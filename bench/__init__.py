"""The chip benchmark: one command runs one cell once (see ``bench/run.py``).

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<traffic>.json`` (which names its
driver in ``drivers/``) and ``layer_metrics/<metric>.py``.
"""
