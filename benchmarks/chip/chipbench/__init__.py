"""Chip benchmark harness: closed-loop clients driving ``repro.Session``
under an HBM budget, end-to-end metrics from the host clock, per-layer
metrics from the program's counters and a profiler trace.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration in ``configs/<name>.json``, the traffic in
``traffic/<name>.json`` and each metric's reader in ``metrics/<name>.py``.
"""
