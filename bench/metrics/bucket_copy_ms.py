"""Device time of the optimizer's bucket copies per traced step, in ms:
the self time of the operations under every bucket's ``gather`` (the
stack of its gradients and states) and ``scatter`` (the updates sliced
back out to the leaves) scopes (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    scoped = scopes.for_run(run)
    return None if scoped is None else scoped.per_step_ms("gather+scatter")
