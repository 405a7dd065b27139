"""Eqn-6 refresh call sites that fell back to the unfused jnp chain, as the
program counts them (``eqn6/fallback/<m>x<n>x<r>``, one per trace of each
bucket's phase-group branch), summed: a count, not a time."""


def read(run):
    return sum(v for k, v in run.counters.items() if k.startswith("eqn6/fallback/"))
