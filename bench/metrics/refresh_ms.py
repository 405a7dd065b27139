"""Device time of one projection refresh of every projected matrix, in ms
(one ``t_update`` cycle's refresh work): the self time under the buckets'
``refresh`` scopes (Eqn 6, Eqn 7) in the traced steps that the traffic's
schedule (``phases``, ``t_update``) refreshes, less the switches' own time
in a step that refreshes nothing, scaled by work (``m n r`` a matrix) from
the matrices those steps refreshed to every projected matrix, so that it
reads the same wherever the window falls on the schedule
(``bench/scopes.refresh_cycle_ms``)."""
from bench import scopes


def read(run):
    scoped = scopes.for_run(run)
    if scoped is None:
        return None
    return scopes.refresh_cycle_ms(scoped, run.cell.traffic["optimizer"], run.shapes)
