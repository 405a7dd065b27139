"""Device time of the optimizer per traced step, in ms: the self time of
the operations under the step's ``optimizer`` scope (``tx.update`` and
``apply_updates``: clipping, every bucket's gather, refresh, update and
scatter, dense Adam), attributed by ``bench/scopes.py``."""
from bench import scopes


def read(run):
    scoped = scopes.for_run(run)
    return None if scoped is None else scoped.per_step_ms(*scopes.OPTIMIZER)
