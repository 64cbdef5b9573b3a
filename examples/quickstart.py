"""Quickstart: open a `repro.Session`, plan a VRAM/HBM budget, generate.

    PYTHONPATH=src python examples/quickstart.py [--arch yi-9b]
"""
import argparse
import os

# step [3] compares tokens across schedules: pin per-op bf16 rounding (see
# tests/conftest.py) so greedy picks can't flip on exact bf16 ties
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_allow_excess_precision" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_allow_excess_precision=false").strip()

import numpy as np  # noqa: E402

from repro import Session  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config, get_smoke_config, list_archs  # noqa: E402
from repro.core import CLI3, InferenceSetting, build_graph  # noqa: E402


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b",
                    choices=list_archs(include_paper=True))
    ap.add_argument("--budget-gb", type=float, default=8.0)
    args = ap.parse_args()

    # 1. plan the FULL config at a budget (planning-only Session: the
    #    install-phase profile runs, no weights are allocated)
    full = get_config(args.arch)
    plan = Session.open(full, CLI3, int(args.budget_gb * 1e9),
                        InferenceSetting(batch=1, context=4096))
    sched = plan.schedule
    print(f"[1] {full.name} ({full.param_count()/1e9:.1f}B) at "
          f"{args.budget_gb}G budget:")
    print(f"    pinned {sched.pinned_bytes/1e9:.2f}G, "
          f"scratch {sched.scratch_bytes/1e9:.2f}G")
    for tier in (1, 512, 4096):
        e = sched.tiers[tier]
        print(f"    tier {tier:5d}: plan={e.plan.name:9s} "
              f"est {e.est_time*1e3:8.2f} ms/iter")
    est = plan.estimates(4096)
    print(f"    est TTFT(4k prompt) {est['ttft_s']:6.2f}s | "
          f"est TPS {est['tps']:6.1f}")

    # 2. a real generation at reduced scale (CPU two-tier simulation):
    #    same Session API, executor built lazily on first generate()
    cfg = get_smoke_config(args.arch)
    if cfg.family not in ("dense", "moe"):
        print(f"[2] family {cfg.family}: planning-only (executor covers "
              "dense/moe)")
        return
    total = sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
    sess = Session.open(cfg, CLI3, int(total * 2.0) + 1,
                        InferenceSetting(batch=2, context=128),
                        db=plan.db, max_seq=128)
    prompts = np.random.RandomState(1).randint(0, cfg.vocab, (2, 16))
    gen = sess.generate(prompts, max_new_tokens=8)
    print(f"[2] {cfg.name}: generated {gen.shape} tokens; sample "
          f"{gen[0].tolist()}")

    # 3. live re-plan: shrink the budget 20x mid-session; only the
    #    pin/evict delta moves (Schedule.diff == executor rebind, §8)
    diff = sess.update_budget(int(total * 0.1) + 1)
    gen2 = sess.generate(prompts, max_new_tokens=8)
    print(f"[3] rebudget 2.0x -> 0.1x weights: moved "
          f"{diff.moved_bytes/1e6:.2f}MB ({diff.summary()})")
    print(f"    tokens identical across budgets: "
          f"{bool(np.array_equal(gen, gen2))}")


if __name__ == "__main__":
    main()
