#!/usr/bin/env python3
"""Record the small chip trace the trace reduction's test reads.

    python3 benchmarks/chip/record_trace.py [--out DIR]

Runs the harness's traced window for a fraction of a second on the test
configuration (``testdata/smoke-dense.json``) on the chip, and writes the
trace, gzipped, and its reduction to ``DIR`` (``testdata/``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import run as bench  # noqa: E402

TESTDATA = bench.CHIP / "testdata"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(TESTDATA))
    out = Path(ap.parse_args(argv).out)
    out.mkdir(parents=True, exist_ok=True)
    bench.prepare_process()
    from chipbench import harness, model, traffic
    from chipbench import trace as tr
    raw = model.load_config(TESTDATA / "smoke-dense.json")
    spec = traffic.load(TESTDATA / "smoke.traffic.json")
    cell = harness.Cell("smoke", 1, raw, spec, [], [],
                        raw["check"][harness.CHECK])
    try:
        device, peaks, system = bench.find_chip(cell)
    except LookupError as e:
        return bench.fail(str(e))
    log_dir = bench.TRACE_DIR / "record"
    shutil.rmtree(log_dir, ignore_errors=True)
    harness.run_cell(cell, 1, 0.3, True, t_process=T_PROCESS, system=system,
                     device=device, peaks=peaks, log_dir=str(log_dir))
    src = tr.find_xplane(str(log_dir))
    dst = out / "chip_trace.xplane.pb.gz"
    with open(src, "rb") as f, gzip.open(dst, "wb") as g:
        g.write(f.read())
    summary = tr.reduce_trace(str(dst))
    (out / "chip_trace.expected.json").write_text(json.dumps(
        dataclasses.asdict(summary), indent=1) + "\n")
    print(json.dumps({"bytes": dst.stat().st_size,
                      "busy_s": summary.busy_s,
                      "window_s": summary.window_s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
