#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds <n,n,...> --seconds <s>

In one process (set-up compiles once), for each seed: one run of the
cell as ``run.py`` makes it, whose mean logit gap (``harness.CHECK``) is
a reading of the program; and, over the same sample of prompts and served
tokens, the control's reading: the reference computed with int8 operands
(``CONTROL``), the mean gap of the tokens it puts first. The fp8 reading
is printed beside it. Prints one JSON line per seed, then the lower
reading (the program's largest) and the upper one (the control's
smallest). The benchmark's own runs never run the control.

Where the upper reading is three times the lower or more, the limit is
set between them, nearer the upper (``limit_between``); every control
reading is then judged by the harness's own ``judge`` and has to come out
not correct, and every program reading correct. The limit and its
readings go to ``checks/<cell>.json``, where ``run.py`` finds it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench  # noqa: E402


CONTROL = "int8"
CONTROLS = ("int8", "fp8")


def limit_between(lower: float, upper: float):
    """A limit with room on both sides, more of it above the lower
    reading (fresh seeds read higher than those it was set from): two
    thirds of the way from lower to upper on a log scale, to two
    significant digits. None where the readings lie too close."""
    if lower <= 0 or upper < 3 * lower:
        return None
    return float(f"{lower ** (1 / 3) * upper ** (2 / 3):.2g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench.prepare_process()
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    try:
        device, peaks, system = bench.find_chip(cell)
    except LookupError as e:
        return bench.fail(str(e))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    program, control, fp8, failed = [], [], [], []
    t = T_PROCESS
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        run = harness.run_cell(cell, seed, args.seconds, False, t_process=t,
                               system=system, device=device, peaks=peaks,
                               controls=CONTROLS)
        r = run.result
        gap = r["check"][harness.CHECK]["value"]
        ctl = r["window"]["controls"]
        program.append(gap)
        failed.append(r["failed"])
        control.append(ctl[CONTROL][harness.CHECK])
        fp8.append(ctl["fp8"][harness.CHECK])
        print(json.dumps({"seed": seed, "program": gap,
                          "control": control[-1], "fp8": fp8[-1],
                          "failed": r["failed"],
                          "attempted": r["attempted"],
                          "metrics": r["metrics"], "window": r["window"]}),
              flush=True)
        del run, r
        gc.collect()
        t = time.perf_counter()
    lower, upper = max(program), min(control)
    limit = limit_between(lower, upper)
    program_correct = [harness.judge(g, limit, f)
                       for g, f in zip(program, failed)]
    control_correct = [harness.judge(g, limit, 0) for g in control]
    readings = {"limit": limit, "lower": lower, "upper": upper,
                "control": CONTROL, "seeds": seeds,
                "program_readings": program, "control_readings": control,
                "fp8_readings": fp8}
    print(json.dumps({"workload": cell.name, **readings,
                      "program_correct": program_correct,
                      "control_correct": control_correct}), flush=True)
    if limit is None or any(control_correct) or not all(program_correct):
        return 1
    harness.CHECKS_DIR.mkdir(exist_ok=True)
    (harness.CHECKS_DIR / f"{cell.name}.json").write_text(
        json.dumps({harness.CHECK: readings}, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
