"""On-chip benchmark of the licensed serving gateway.

``bench/run.py`` runs one cell of ``BENCHMARK.json`` once.  Everything
that belongs to one model configuration, traffic mix or per-layer metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: published sizes, cuts, gateway settings;
* ``references/<reference>.py``: the plain float32 reference a config
  names (its ``"reference"`` key);
* ``traffic/<traffic>.json``: parameters read by ``traffic.py``;
* ``metrics/<metric>.py``: one reader per metric.

Shared yardstick code: ``weights.py`` (seeded weights, also regenerated
by the reference), ``client.py`` (open-loop client and end-to-end
reductions), ``trace.py`` (profiler-trace reduction), ``costs.py``
(operations and bytes from shapes, peaks table), ``check.py`` (the
comparison that decides ``correct``).
"""
