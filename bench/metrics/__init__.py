"""One reader per per-layer metric, found by the metric's name.

``bench/metrics/<name>.py`` defines ``read(rec) -> float | None``, where
``rec`` is the window's ``harness.Record``.  A reader that finds nothing to
read returns None, and the metric is left out of the result line.
"""
