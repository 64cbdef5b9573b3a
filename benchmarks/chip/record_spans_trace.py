#!/usr/bin/env python3
"""Record the small chip trace with the program's spans that the span
reduction's test reads.

    python3 benchmarks/chip/record_spans_trace.py [--out DIR]

Runs ``record_trace.py``'s traced window on the chip (the test
configuration at half its weight bytes, so it streams and fetches at
use) into a directory of its own, and writes its trace, gzipped, as
``chip_trace_spans.xplane.pb.gz`` and its span reduction as
``chip_trace_spans.expected.json`` to ``DIR`` (``testdata/``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
from pathlib import Path

import record_trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(record_trace.TESTDATA))
    out = Path(ap.parse_args(argv).out)
    out.mkdir(parents=True, exist_ok=True)
    scratch = record_trace.bench.TRACE_DIR / "record_spans"
    shutil.rmtree(scratch, ignore_errors=True)
    rc = record_trace.main(["--out", str(scratch)])
    if rc:
        return rc
    from chipbench import spans
    dst = out / "chip_trace_spans.xplane.pb.gz"
    shutil.copyfile(scratch / "chip_trace.xplane.pb.gz", dst)
    summary = spans.reduce_spans(str(dst))
    (out / "chip_trace_spans.expected.json").write_text(json.dumps(
        dataclasses.asdict(summary), indent=1) + "\n")
    print(json.dumps({"bytes": dst.stat().st_size, "counts": summary.counts,
                      "top_gaps": summary.top_gaps(5)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
