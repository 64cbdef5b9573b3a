"""End-to-end driver (the paper's kind): serve batched requests under a
VRAM/HBM budget with pipelined sharding — plan, chunk-prefill, decode —
through the `repro.Session` front door, including a live mid-serve
``update_budget`` swap (the IGI "game claimed the VRAM" scenario,
DESIGN.md §8).

Runs a reduced-config MoE model for real on CPU; weights stream between the
two simulated memory tiers exactly as the schedule dictates, and the
generated tokens are verified to be identical across budgets AND across the
live swap.

    PYTHONPATH=src python examples/serve_vram_budget.py [--arch qwen30b-a3b]
"""
import argparse
import os
import time

# the demo asserts token identity across schedules that compile different
# prefill chunkings — pin per-op bf16 rounding like tests/conftest.py does,
# so greedy picks can't flip on exact ties (must precede jax backend init)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_allow_excess_precision" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_allow_excess_precision=false").strip()

from repro import Session  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.core import (CLI2, InferenceSetting, build_graph,  # noqa: E402
                        run_install)
from repro.core.serving import random_requests  # noqa: E402


def make_requests(cfg, batch, prompt_len, new_tokens, seed=1):
    return random_requests(cfg.vocab, batch, prompt_len, new_tokens,
                           seed=seed)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen30b-a3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    assert cfg.family in ("dense", "moe"), "serving demo covers dense/moe"
    db = run_install(CLI2, quick=True)
    total = sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
    setting = InferenceSetting(batch=args.batch, context=128)

    ref_tokens = None
    for frac in (2.0, 0.5, 0.1):
        sess = Session.open(cfg, CLI2, int(total * frac) + 1, setting,
                            db=db, max_seq=128)
        reqs = make_requests(cfg, args.batch, args.prompt_len,
                             args.new_tokens)
        t0 = time.perf_counter()
        sess.serve(reqs, max_batch=args.batch)
        dt = time.perf_counter() - t0
        st = sess.stats()
        gen = [r.generated for r in reqs]
        if ref_tokens is None:
            ref_tokens = gen
        same = gen == ref_tokens
        print(f"budget={frac:4.1f}x weights ({total*frac/1e6:7.1f}MB): "
              f"served {args.batch} reqs in {dt*1e3:7.1f}ms "
              f"| streamed {st['executor']['streamed_bytes']/1e6:7.1f}MB, "
              f"engines {st['executor']['engine_calls']}, "
              f"tokens identical across budgets: {same}")

    # live swap: start at 2x, drop to 0.1x with requests IN FLIGHT —
    # in-flight slots keep decoding, and only the pin/evict delta moves
    sess = Session.open(cfg, CLI2, int(total * 2.0) + 1, setting,
                        db=db, max_seq=128)
    reqs = make_requests(cfg, args.batch, args.prompt_len, args.new_tokens)
    sess.serve(reqs, max_batch=args.batch, max_iterations=2)
    diff = sess.update_budget(int(total * 0.1) + 1)
    sess.serve([])  # drain the in-flight slots under the new schedule
    same = [r.generated for r in reqs] == ref_tokens
    print(f"live rebudget 2.0x -> 0.1x mid-serve: moved "
          f"{diff.moved_bytes/1e6:.2f}MB ({diff.summary()}); "
          f"remaining tokens identical to uninterrupted runs: {same}")
    print("NOTE: wall-clock here is this container's CPU simulating both "
          "tiers; the schedule choices + streamed bytes are the signal. "
          "Planner estimates for real client systems: benchmarks/table4.csv")


if __name__ == "__main__":
    main()
