"""On-chip benchmark of the hybrid dataflow + SPMD runtime.

``run.py`` runs one cell of ``BENCHMARK.json`` once. Everything that belongs
to one configuration, traffic mix, job kind or per-layer metric is a file of
its own, found by name: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``jobs/<job>.py`` and ``metrics/<metric>.py``.
"""
