"""The HMGI chip benchmark: one cell of ``BENCHMARK.json`` per run.

``run.py`` is the entry point. Everything a cell is made of lives in files
found by name: the deployment in ``configs/<config>.json``, the traffic mix
in ``traffic/<traffic>.json``, the limits of the output check in
``limits/<cell>.json`` and each per-layer metric in
``metrics/<metric>.py``.
"""
