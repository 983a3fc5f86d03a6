"""The on-chip benchmark: one harness, cells declared in ``BENCHMARK.json``.

``bench/run.py`` runs one cell once.  ``bench/lib`` is the yardstick (the
matrices, the traffic generator, the reference and its limits, the peaks,
the floor bytes and the trace reduction); ``configs``, ``traffic``,
``kinds``, ``families`` and ``metrics`` hold one file per configuration,
mix, kind of cell, pattern family and per-layer reader, found by name
(``bench.lib.registry``).
"""
