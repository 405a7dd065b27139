"""Shared test configuration: a deterministic hypothesis profile.

Every property test draws the same examples on every run
(``derandomize``), and no example database is read or written, so the
suite's pass count does not drift between runs or machines. Examples that
once failed are pinned on their tests with ``@example``.
"""
from hypothesis import settings

settings.register_profile("repro", derandomize=True, database=None)
settings.load_profile("repro")
