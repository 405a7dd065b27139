"""Seconds from process start to the window's first step: start-up, the
device, weights and feed, compile or cache load, and the warm-up steps."""


def read(run):
    return run.setup_s
