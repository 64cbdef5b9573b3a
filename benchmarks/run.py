# One function per paper table. Prints ``name,metric,value`` CSV lines.
"""Benchmark harness: one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run                  # everything
    PYTHONPATH=src python -m benchmarks.run table4           # one artifact
    PYTHONPATH=src python -m benchmarks.run --only table4    # same, explicit

Every run consolidates its suites' ``name,metric,value`` output into
``benchmarks/results/BENCH_SUMMARY.json`` keyed by suite. The file is
merged on write — a partial run (``--only spec_decode``) refreshes just
its own suites and leaves every other suite's last recorded results
intact, so the summary converges to a full picture across CI shards.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

from benchmarks import (bench_faults, bench_figure2, bench_figure3,
                        bench_figure4, bench_figure5, bench_figure6,
                        bench_gateway, bench_kv_paged, bench_moe_experts,
                        bench_oracle, bench_overlap, bench_prefill,
                        bench_quant_stream, bench_rebudget, bench_serving,
                        bench_spec_decode, bench_table4, bench_table5,
                        bench_table8, bench_table9, roofline)
from benchmarks.common import RESULTS
from repro.compile_cache import enable_compile_cache

SUITES = {
    "overlap": bench_overlap.run,
    "serving": bench_serving.run,
    "gateway": bench_gateway.run,
    "rebudget": bench_rebudget.run,
    "moe_experts": bench_moe_experts.run,
    "prefill": bench_prefill.run,
    "quant_stream": bench_quant_stream.run,
    "kv_paged": bench_kv_paged.run,
    "spec_decode": bench_spec_decode.run,
    "faults": bench_faults.run,
    "table4": bench_table4.run,
    "table5": bench_table5.run,
    "figure2": bench_figure2.run,
    "figure3": bench_figure3.run,
    "figure4": bench_figure4.run,
    "figure5": bench_figure5.run,
    "figure6": bench_figure6.run,
    "table8": bench_table8.run,
    "table9": bench_table9.run,
    "oracle": bench_oracle.run,
    "roofline": roofline.run,
}

SUMMARY = os.path.join(RESULTS, "BENCH_SUMMARY.json")


class _Tee(io.TextIOBase):
    """Mirror suite stdout to the terminal while keeping a copy for the
    metric scrape — suites stay plain print()-based."""

    def __init__(self, real):
        self.real = real
        self.buf = io.StringIO()

    def write(self, s):
        self.real.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.real.flush()


def _scrape_metrics(text: str) -> list:
    """Pull ``name,metric,value`` lines out of a suite's output. Values
    parse to numbers when they can; everything else stays a string."""
    rows = []
    for line in text.splitlines():
        parts = line.strip().split(",")
        if len(parts) != 3 or not parts[0] or " " in parts[0]:
            continue
        name, metric, value = parts
        try:
            value = float(value)
            if value.is_integer():
                value = int(value)
        except ValueError:
            pass
        rows.append({"name": name, "metric": metric, "value": value})
    return rows


def _merge_summary(results: dict) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    summary = {}
    if os.path.exists(SUMMARY):
        try:
            with open(SUMMARY) as f:
                summary = json.load(f)
        except (OSError, json.JSONDecodeError):
            summary = {}  # corrupt/partial file: rebuild from this run
    summary.update(results)
    with open(SUMMARY, "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
        f.write("\n")
    return SUMMARY


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("suites", nargs="*", metavar="suite",
                    help=f"suites to run (default: all); one of "
                         f"{', '.join(SUITES)}")
    ap.add_argument("--only", action="append", default=[], metavar="suite",
                    help="run only this suite (repeatable); combines with "
                         "positional suite names")
    ap.add_argument("--list", action="store_true",
                    help="print the suite names and exit")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(SUITES))
        return
    enable_compile_cache()
    names = list(dict.fromkeys(args.suites + args.only)) or list(SUITES)
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s): {', '.join(unknown)}; "
                 f"choose from {', '.join(SUITES)}")
    results = {}
    for name in names:
        t0 = time.time()
        print(f"=== {name} ===")
        tee = _Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            SUITES[name]()
        dt = time.time() - t0
        print(f"{name},seconds,{dt:.1f}")
        results[name] = {
            "seconds": round(dt, 3),
            "smoke": bool(os.environ.get("REPRO_BENCH_SMOKE")),
            "metrics": _scrape_metrics(tee.buf.getvalue()),
        }
    path = _merge_summary(results)
    print(f"benchmarks,summary,{path}")
    print("benchmarks,done,ok")


if __name__ == "__main__":
    main()
