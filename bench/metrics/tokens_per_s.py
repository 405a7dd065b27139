"""Tokens trained per second: every token of the window's steps over the
window's wall time (host clock), refresh steps included."""


def read(run):
    return run.tokens_per_step * len(run.step_s) / run.window_s
