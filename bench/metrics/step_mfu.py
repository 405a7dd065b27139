"""Model FLOP utilisation of the step on the device, in percent of the
chip's bf16 peak: the operations a training step requires
(``flops_per_token`` of the reference module the configuration names; no
recomputation counted) times the traced steps, over the time in which an
operation ran on the device in them (the trace's busy time), so that host
idle is left to ``device_idle_share``."""


def read(run):
    if run.trace is None:
        return None
    work = run.flops_per_step * run.traced_steps
    return 100.0 * work / run.trace.busy_s / run.peaks["bf16_flop_per_s"]
