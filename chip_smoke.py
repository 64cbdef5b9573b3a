#!/usr/bin/env python3
"""Bring-up smoke test: serve qwen2-0.5b at its published width on one TPU
through ``repro.Session``, with random weights made from a seed.

    python3 chip_smoke.py

Every phase runs in this one process, which starts no other (a chip
belongs to one process at a time):

1. device  -- JAX must find a TPU; its ``device_kind`` picks the planner's
   ``SystemConfig``. No TPU, or a kind without a row, exits 2 before any
   work.
2. bf16    -- ``Session.open`` at an ample budget (every sub-layer pinned)
   and at a tight one (the plan streams), four seeded requests each; then
   a live ``update_budget`` from ample to tight mid-serve. The greedy
   tokens must agree bit for bit across all three.
3. donation -- with stacked KV and with paged KV, one request's prefill
   fails after an attention step donated the shared cache; it must fail
   alone, and the others must match a clean run bit for bit.
4. int4    -- ``weight_quant="int4"`` at a budget where the plan streams
   FFNs, which must run through the Pallas fused-dequant kernel.
5. kernels -- ``streamed_matmul`` and ``streamed_matmul_int4`` against
   their jnp references (``@`` and ``dequant_int4``) at real FFN shapes.

Each serving phase fails if a request errs or comes back short, if the session
degraded, if a weight copy failed, or if device memory in use exceeds the
plan's pinned + scratch + KV bytes plus the executor's embed/head bytes by
more than a tenth of the model's weight bytes. The lines before the last
are bring-up observations, not benchmark numbers. The last line of
standard output is one JSON object naming the device, printed only when
every phase passed (exit 0); otherwise the exit code is 1.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
import traceback

ARCH = "qwen2-0.5b"
BATCH = 4              # requests per phase, served as one batch
PROMPT_LEN = 64
NEW_TOKENS = 12
MAX_SEQ = 128
AMPLE, TIGHT = 2.0, 0.5            # budgets as fractions of weight bytes
INT4_FRACS = (0.5, 0.4, 0.3, 0.25, 0.2)
HBM_SLACK = 0.10       # allowed excess over the plan, in weight bytes
KERNEL_TOL = 2e-2      # max |kernel - ref| / max |ref|
KERNEL_M = 64          # activation rows in the kernel check


class PhaseFailed(Exception):
    pass


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling (a persistent
    cache hit counts only its read), from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def weight_bytes(cfg) -> int:
    from repro.core import build_graph
    return sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))


def open_session(cfg, system, db, budget, params=None, **kw):
    from repro import Session
    from repro.core import InferenceSetting
    return Session.open(cfg, system, int(budget),
                        InferenceSetting(batch=BATCH, context=MAX_SEQ),
                        db=db, max_seq=MAX_SEQ, params=params, **kw)


def streams_ffn(sess) -> bool:
    """True when the decode tier's plan streams an FFN on the device."""
    sched = sess.schedule
    plan = sched.tiers[sched.pick_decode_tier(BATCH)].plan
    return any(p.sub.kind == "ffn" and p.streamed and p.engine == "gpu"
               for p in plan.placements)


def serve_phase(name, sess, *, seed=1, swap_to=None, require_stream=False,
                require_pallas=None, meter=None, device=None):
    """Serve BATCH seeded requests through ``sess`` (with a live
    ``update_budget(swap_to)`` after two iterations when given), check the
    failure rules, print the observations and return the greedy tokens."""
    from repro.core.serving import random_requests
    cfg = sess.cfg
    reqs = random_requests(cfg.vocab, BATCH, PROMPT_LEN, NEW_TOKENS,
                           seed=seed)
    c0 = meter.seconds if meter else 0.0
    t0 = time.perf_counter()
    swap = None
    if swap_to is None:
        sess.serve(reqs, max_batch=BATCH)
    else:
        sess.serve(reqs, max_batch=BATCH, max_iterations=2)
        diff = sess.update_budget(int(swap_to))
        swap = {"moved_bytes": diff.moved_bytes, "diff": diff.summary()}
        sess.serve([])
    wall = time.perf_counter() - t0
    st = sess.stats()
    ex, deg, sv = st["executor"], st["degradation"], st["serving"]
    sched = sess.schedule
    total = weight_bytes(cfg)
    decode_tier = sched.pick_decode_tier(BATCH)
    obs = {
        "budget_bytes": sess.budget_bytes,
        "weight_bytes": total,
        "decode_tier": decode_tier,
        "decode_plan": sched.tiers[decode_tier].plan.name,
        "tiers_used": sv["tiers_used"],
        "streamed_bytes": ex["streamed_bytes"],
        "staged_bytes": ex["staged_bytes"],
        "copy_s_hidden": ex["copy_s_hidden"],
        "copy_s_exposed": ex["copy_s_exposed"],
        "copy_retries": deg["copy_retries"],
        "wall_s": wall,
        "compile_s": (meter.seconds - c0) if meter else None,
        "ffn_paths": ex["ffn_paths"],
        "plan_pinned_bytes": sched.pinned_bytes,
        "plan_scratch_bytes": sched.scratch_bytes,
        "plan_kv_bytes": sched.kv_pool_bytes,
        "hbm_bytes": st["hbm_bytes"],
        "first_request_tokens": list(reqs[0].generated),
    }
    if swap:
        obs["swap"] = swap
    fails = []
    for r in reqs:
        if r.error is not None:
            fails.append(f"request {r.rid} failed: {r.error}")
        elif len(r.generated) != r.max_new_tokens:
            fails.append(f"request {r.rid}: {len(r.generated)} tokens of "
                         f"{r.max_new_tokens}")
        elif not all(0 <= t < cfg.vocab for t in r.generated):
            fails.append(f"request {r.rid}: token outside the vocabulary")
    if deg["level"] > 0:
        fails.append(f"session degraded to level {deg['level']}")
    if deg["copy_failures"] > 0:
        fails.append(f"{deg['copy_failures']} weight copies failed")
    if require_stream and ex["streamed_bytes"] <= 0:
        fails.append("the plan streamed nothing")
    if require_pallas and not ex["ffn_paths"].get(require_pallas):
        fails.append(f"no FFN ran through {require_pallas}: "
                     f"{ex['ffn_paths']}")
    mem = device.memory_stats() if device is not None else None
    if mem:
        bound = (sched.pinned_bytes + sched.scratch_bytes
                 + sched.kv_pool_bytes + st["hbm_bytes"]["outputs"]
                 + HBM_SLACK * total)
        obs.update(bytes_in_use=mem.get("bytes_in_use"),
                   peak_bytes_in_use=mem.get("peak_bytes_in_use"),
                   hbm_bound=int(bound))
        if mem.get("bytes_in_use", 0) > bound:
            fails.append(f"device bytes in use {mem['bytes_in_use']} exceed "
                         f"the plan's {int(bound)}")
    print(f"[{name}] " + json.dumps(obs), flush=True)
    if fails:
        raise PhaseFailed(f"{name}: " + "; ".join(fails))
    return [list(r.generated) for r in reqs]


def bf16_phases(cfg, system, db, *, meter=None, device=None):
    """Ample, tight and live-swap serving of the bf16 model; the greedy
    tokens must be identical across all three."""
    total = weight_bytes(cfg)
    kw = dict(meter=meter, device=device)
    ample = open_session(cfg, system, db, total * AMPLE)
    tokens = {"ample": serve_phase("bf16-ample", ample, **kw)}
    params = ample.params
    release(ample)
    tight = open_session(cfg, system, db, total * TIGHT, params=params)
    tokens["tight"] = serve_phase("bf16-tight", tight, require_stream=True,
                                  **kw)
    release(tight)
    swap = open_session(cfg, system, db, total * AMPLE, params=params)
    tokens["swap"] = serve_phase("bf16-swap", swap, swap_to=total * TIGHT,
                                 **kw)
    release(swap)
    if not tokens["ample"] == tokens["tight"] == tokens["swap"]:
        raise PhaseFailed(f"bf16 greedy tokens differ across budgets: "
                          f"{tokens}")
    return tokens, params


def fail_second_prefill(ex):
    """Make the executor's second prefill raise in its first FFN, after
    its first attention step has consumed the shared KV cache (on an
    accelerator that step donates, and so deletes, the buffers it was
    given)."""
    prefill, ffn_step = ex.prefill, ex.engine.ffn_step
    state = {"calls": 0, "armed": False}

    def counting_prefill(*a, **kw):
        state["calls"] += 1
        state["armed"] = state["calls"] == 2
        return prefill(*a, **kw)

    def failing_ffn(w, x, streamed=False):
        if state["armed"]:
            state["armed"] = False
            raise RuntimeError("injected mid-prefill failure")
        return ffn_step(w, x, streamed=streamed)

    ex.prefill, ex.engine.ffn_step = counting_prefill, failing_ffn


def donation_phase(cfg, system, db, params, *, kv_layout, reference=None,
                   meter=None, device=None):
    """A request whose prefill fails after an attention step consumed the
    shared cache (stacked KV or page pools) fails alone; the others must
    finish with the tokens of a clean run, read off the live buffers.
    ``reference`` is that clean run's tokens; without it a clean session
    of the same layout serves them first."""
    from repro.core.serving import random_requests
    budget = weight_bytes(cfg) * AMPLE
    if reference is None:
        clean = open_session(cfg, system, db, budget, params=params,
                             kv_layout=kv_layout)
        reference = serve_phase(f"{kv_layout}-clean", clean, meter=meter,
                                device=device)
        release(clean)
    sess = open_session(cfg, system, db, budget, params=params,
                        kv_layout=kv_layout)
    try:
        fail_second_prefill(sess.executor)
        reqs = random_requests(cfg.vocab, BATCH, PROMPT_LEN, NEW_TOKENS,
                               seed=1)
        sess.serve(reqs, max_batch=BATCH)
        failed = [r.rid for r in sess.batcher().failed]
    finally:
        release(sess)
    survivors = [i for i in range(BATCH) if i != 1]
    match = all(reqs[i].generated == reference[i] for i in survivors)
    print(f"[donation-{kv_layout}] " + json.dumps({
        "failed": failed, "expected_failed": [reqs[1].rid],
        "survivor_errors": [reqs[i].error for i in survivors],
        "survivors_match_clean_run": match}), flush=True)
    if failed != [reqs[1].rid] or not match or \
            any(reqs[i].error for i in survivors):
        raise PhaseFailed(f"donation-{kv_layout}: a failed prefill "
                          f"disturbed the other requests")
    return failed


def int4_phase(cfg, system, db, *, require_pallas="pallas_int4", meter=None,
               device=None):
    """The int4 model at the largest budget whose decode plan streams an
    FFN, so the fused-dequant kernel serves it."""
    cfg = cfg.replace(weight_quant="int4")
    total = weight_bytes(cfg)
    for frac in INT4_FRACS:
        sess = open_session(cfg, system, db, total * frac)
        if streams_ffn(sess):
            break
    else:
        raise PhaseFailed(f"int4: no budget in {INT4_FRACS} streams an FFN")
    try:
        return serve_phase("int4", sess, require_stream=True,
                           require_pallas=require_pallas, meter=meter,
                           device=device)
    finally:
        release(sess)


def kernel_phase(bf16_shape=(4096, 14336), int4_shapes=((896, 4864),
                                                        (4864, 896)),
                 m=KERNEL_M, interpret=False):
    """Pallas streamed matmuls against their jnp references; the error is
    the largest |kernel - ref| over the largest |ref|, at most KERNEL_TOL."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.streamed_matmul import (dequant_int4, quantize_int4,
                                               streamed_matmul,
                                               streamed_matmul_int4)
    key = jax.random.PRNGKey(7)
    hi = jax.lax.Precision.HIGHEST

    def err(out, ref):
        out, ref = out.astype(jnp.float32), ref.astype(jnp.float32)
        return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))

    def operands(k, n, i):
        kx, kw = jax.random.split(jax.random.fold_in(key, i))
        x = jax.random.normal(kx, (m, k), jnp.bfloat16)
        w = 0.02 * jax.random.normal(kw, (k, n), jnp.float32)
        return x, w

    errors = {}
    x, w = operands(*bf16_shape, 0)
    w = w.astype(jnp.bfloat16)
    ref = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  precision=hi)
    errors[f"bf16 {bf16_shape}"] = err(
        streamed_matmul(x, w, interpret=interpret), ref)
    for i, shape in enumerate(int4_shapes, 1):
        x, w = operands(*shape, i)
        packed, scales, zeros = quantize_int4(w)
        ref = jnp.dot(x.astype(jnp.float32),
                      dequant_int4(packed, scales, zeros), precision=hi)
        errors[f"int4 {shape}"] = err(
            streamed_matmul_int4(x, packed, scales, zeros,
                                 interpret=interpret), ref)
    print("[kernels] " + json.dumps({"m": m, "tol": KERNEL_TOL,
                                     "max_rel_err": errors}), flush=True)
    bad = {k: v for k, v in errors.items() if not v <= KERNEL_TOL}
    if bad:
        raise PhaseFailed(f"kernels beyond {KERNEL_TOL}: {bad}")
    return errors


def release(sess):
    """Drop a session's executor and batcher and collect them, so the next
    phase's device memory holds only its own plan."""
    sess.close()
    gc.collect()


def prepare_process():
    """Settings that must precede JAX's start: per-op bf16 rounding, which
    bit-identical greedy tokens across budgets need (as in the examples
    and tests), and the checkout's ``src`` on the import path."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_allow_excess_precision" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_allow_excess_precision=false").strip()
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def main() -> int:
    prepare_process()
    try:
        import jax
        from repro.compile_cache import enable_compile_cache
        from repro.configs import get_config
        from repro.core import run_install, system_for_device_kind
    except ImportError as e:
        print(f"[smoke] cannot import the program: {e}", file=sys.stderr)
        return 2
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"[smoke] no TPU: JAX found {device.platform!r} "
              f"({device.device_kind})", file=sys.stderr)
        return 2
    try:
        system = system_for_device_kind(device.device_kind)
    except KeyError as e:
        print(f"[smoke] {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    meter.install()
    print("[device] " + json.dumps({
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()), "system": system.name,
        "compile_cache": cache_dir}), flush=True)
    cfg = get_config(ARCH)
    db = run_install(system, quick=True)
    t0 = time.perf_counter()
    failures = []

    def run(phase, *args, **kw):
        try:
            return phase(*args, **kw)
        except Exception as e:
            failures.append(e)
            traceback.print_exc()

    kw = dict(meter=meter, device=device)
    bf16 = run(bf16_phases, cfg, system, db, **kw)
    if bf16 is not None:
        tokens, params = bf16
        run(donation_phase, cfg, system, db, params, kv_layout="stacked",
            reference=tokens["ample"], **kw)
        run(donation_phase, cfg, system, db, params, kv_layout="paged",
            **kw)
        del params
    run(int4_phase, cfg, system, db, **kw)
    run(kernel_phase)
    print("[smoke] " + json.dumps({
        "wall_s": time.perf_counter() - t0, "compile_s": meter.seconds,
        "compile_cache_hits": meter.cache_hits,
        "failures": [str(e) for e in failures]}), flush=True)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
