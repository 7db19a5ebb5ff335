"""The benchmark: cells of (model configuration x training mix) driven
through ``Trainer.train()`` on the TPU, one result line per run.

``python3 -m benchmark.run --workload <cell> --seed N --seconds S --trace 0|1``
(``BENCHMARK.json`` at the repo root names the cells, metrics and bounds).
Everything that belongs to one configuration, one mix or one per-layer
metric is a file found by the name in ``BENCHMARK.json`` — a later PR adds
files and entries and edits nothing here (``manifest.py``). The yardstick
lives here too, where a PR that claims a gain may not touch it: the
window arithmetic (``window.py``), the trace reduction (``trace.py``), the
peak table (``peaks.json``), the FLOPs and bytes functions (``flops/``),
the plain float32 references (``reference/``) and the comparison that
decides ``correct`` (``correct.py``).

Importing this package (and ``manifest``, ``window``, ``run``) never
imports jax; the modules that need it import it inside their functions.
"""
