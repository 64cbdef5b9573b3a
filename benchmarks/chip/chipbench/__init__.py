"""Chip benchmark harness: closed-loop clients driving ``repro.Session``
under an HBM budget, end-to-end metrics from the host clock, per-layer
metrics from the program's counters and a profiler trace.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration in ``configs/<name>.json``, its model family (weights,
reference and operation counts) in ``chipbench/families/<family>.py``,
the traffic in ``traffic/<name>.json``, the correctness limit in
``checks/<cell>.json`` and each metric's reader in ``metrics/<name>.py``.
A new family, configuration, mix or metric is a new file; the family
contract is in ``chipbench/families/__init__.py``.
"""
