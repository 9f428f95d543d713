"""One reader per metric, found by the metric's name in BENCHMARK.json.

A reader is ``<name>.py`` with ``read(run) -> float | None``.  ``run``
carries the cell's ``config`` and ``mix``, the driver's ``window``
(``bench/driver.py``), the trace ``Reduction`` of a ``--trace 1`` run (else
None), the chip's ``peaks``, ``chips`` and ``setup_s``.  A reader that
finds nothing to read returns None and the metric is left out of the line.
"""
