#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the cell's chips.
The cell (configuration, traffic mix, metrics) is looked up by name in
``BENCHMARK.json``. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics with ``--trace 0``, the per-layer ones with ``--trace 1``),
``device`` and, when traced, ``breakdown``; its last key, ``check``, holds
each number compared with its limit, which also end standard error.

Exits 2 without a result when JAX finds no TPU, fewer chips than the cell
needs, or a chip with no row in the peak table.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP = Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench" / "traces"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_process():
    """The compile cache at a fixed path inside the checkout (given to the
    program through the variable it reads), and the program's and the
    harness's sources on the import path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (CHIP, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def fail(msg: str) -> int:
    print(f"[chipbench] {msg}", file=sys.stderr)
    return 2


def find_chip(cell):
    """The first TPU and its peak row, or a reason there is none."""
    import jax
    from chipbench.peaks import peaks_for
    from repro.core import system_for_device_kind
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise LookupError(f"no TPU: JAX found {dev.platform!r} "
                          f"({dev.device_kind})")
    if len(devices) < cell.chips:
        raise LookupError(f"{cell.name} needs {cell.chips} chips, JAX "
                          f"found {len(devices)}")
    try:
        return dev, peaks_for(dev.device_kind), \
            system_for_device_kind(dev.device_kind)
    except KeyError as e:
        raise LookupError(str(e)) from None


def main(argv=None) -> int:
    args = parse(argv)
    prepare_process()
    try:
        from chipbench import harness
        import repro  # noqa: F401
    except ImportError as e:
        return fail(f"cannot import the harness or the program: {e}")
    cell = harness.load_cell(args.workload)
    try:
        device, peaks, system = find_chip(cell)
    except LookupError as e:
        return fail(str(e))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    log_dir = None
    if args.trace:
        log_dir = TRACE_DIR / args.workload
        shutil.rmtree(log_dir, ignore_errors=True)
        log_dir = str(log_dir)
    run = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS, system=system,
                           device=device, peaks=peaks, log_dir=log_dir)
    sys.stdout.flush()
    print(json.dumps(run.result), flush=True)
    for line in run.check_lines:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
