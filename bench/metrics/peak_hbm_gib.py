"""``peak_bytes_in_use`` of the fullest chip after the window, before the
check runs, in GiB."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30
