"""One reader per metric, ``<metric name>.py``, each with ``read(run)``.

``read`` takes the :class:`bench.harness.Run` of one run and returns the
metric's value, or ``None`` where the run holds nothing to read it from;
the harness then leaves the metric out of the result line.
"""
