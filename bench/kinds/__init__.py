"""Kinds of cell: one module per request type, found by name.

A traffic mix's ``request`` names its kind: the module
``bench/kinds/<request>.py``, which ``bench.lib.registry.load_kind`` loads
from its file.  A request with no module fails at once, naming the path
looked for.  The harness (``bench.lib.harness``) knows no kind; it calls
these hooks, each a function of the module:

* ``build(setup) -> (system, ops, pool, a)``: from a
  ``harness.Setup`` (the configuration, the mix, the seed, the devices,
  the scale, the cache and benchmark directories, the log, and helpers for
  the configuration's matrix and plan cache), the system under test, its
  prepared operators by block width (their plans are logged, and evicted
  after the window), the device-resident pool of requests, and the host
  matrix whose rows, columns and nonzeros ``RunRecord`` carries.  It warms
  up every program the window will run: ``setup_s`` ends when it returns.
* ``drive(cell, seconds, seed) -> outcome``: the measured window, one call
  of the generator in ``bench.lib.traffic``.  The harness starts and stops
  the profiler and the compile counter around it.
* ``reduce(cell, outcome, log) -> harness.Reduced``: the end-to-end values
  under the benchmark's metric names (``setup_s`` aside), the counters the
  per-layer readers read, the attempted and failed counts, and the sample
  ``check`` compares, copied to the host.
* ``close(cell)``: stop what ``build`` started (threads, queues).
* ``check(cell, a64, reduced, log) -> dict``: each compared number beside
  its limit (``harness.compared``), against ``bench.lib.reference``;
  ``a64`` is the cell's matrix in float64.  ``correct`` is every number
  at or under its limit.
* ``control(cell, a64, reduced) -> dict`` (optional): the control's
  readings on the same sample, for ``bench/calibrate.py readings
  --control``; the benchmark's own runs never call it.

A kind that brings a new kernel or program brings the function that counts
its floor bytes (``bench/lib/floor_bytes.py`` holds the existing ones), and
its per-layer readers read ``RunRecord.counters``, ``.trace`` and
``.spans``.  A matrix family that ``bench.lib.suite`` lacks is a file
``bench/families/<family>.py``; a kind may build further matrices itself
(a multigrid hierarchy), and ``RunRecord`` counts the one ``build``
returns.
"""
