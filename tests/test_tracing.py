"""The program's spans and HBM counters, on the CPU.

A tiny tight-budget ``Session`` (it streams sub-layers through the
prefetcher and fetches CPU-engine ones at use) serves under
``jax.profiler`` with a live re-plan in the middle, each ``step()``
wrapped the way the chip benchmark wraps it. The trace must hold every
span the program emits, nested layer in layer, with the staging
worker's spans on a thread of their own tagged with the pass that
caused them; tracing must not change a token; and ``hbm_bytes()`` must
count the arrays its owners hold."""
from __future__ import annotations

import glob
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

from repro import Session
from repro.configs import get_smoke_config
from repro.core import CLI2, InferenceSetting, build_graph, run_install
from repro.core.prefetch import tree_nbytes
from repro.core.serving import Request

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

from chipbench import spans as bench_spans  # noqa: E402
from chipbench import trace as bench_trace  # noqa: E402

BATCH = 2


def serve(db, trace_dir=None):
    """qwen2-0.5b at smoke width (tied head) at half its weight bytes:
    four requests, the budget halved again after three steps. Returns
    the session, its batcher and each request's tokens."""
    cfg = get_smoke_config("qwen2-0.5b")
    total = sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
    sess = Session.open(cfg, CLI2, int(total * 0.5) + 1,
                        InferenceSetting(batch=BATCH, context=64), db=db,
                        max_seq=64)
    batcher = sess.batcher(max_batch=BATCH)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=5 + 4 * i)
                    .astype(np.int32), max_new_tokens=4 + i)
            for i in range(4)]
    batcher.submit(reqs)
    if trace_dir is not None:
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        n = 0
        while batcher.has_work:
            with TraceAnnotation(bench_trace.STEP):
                batcher.step()
            n += 1
            if n == 3:
                sess.update_budget(int(total * 0.25) + 1)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return sess, batcher, [list(r.generated) for r in reqs]


@pytest.fixture(scope="module")
def db():
    return run_install(CLI2, quick=True)


@pytest.fixture(scope="module")
def traced(db, tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    sess, batcher, tokens = serve(db, d)
    path = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)[0]
    spans, main = bench_spans.program_spans(ProfileData.from_file(path))
    return sess, batcher, tokens, spans, main


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def _parent(span, spans, name):
    return [p for p in spans if p.name == name and p.line == span.line
            and _inside(span, p)]


def test_the_program_emits_exactly_the_spans_the_benchmark_knows(traced):
    _, _, _, spans, main = traced
    assert main is not None
    assert {s.name for s in spans} == bench_spans.PROGRAM_SPANS


def test_waits_nest_in_a_pass_in_a_step_on_the_serving_thread(traced):
    _, _, _, spans, main = traced
    waits = [s for s in spans if s.name in ("prefetch.acquire",
                                            "executor.fetch_at_use",
                                            "executor.pass_end")]
    assert {s.name for s in waits} == {"prefetch.acquire",
                                       "executor.fetch_at_use",
                                       "executor.pass_end"}
    for w in waits:
        assert w.line == main, w
        passes = _parent(w, spans, "executor.pass")
        assert len(passes) == 1, w
        assert _parent(passes[0], spans, "serving.step"), passes[0]
    for s in spans:
        if s.name in ("serving.admit", "serving.decode"):
            assert s.line == main and _parent(s, spans, "serving.step")
    for s in spans:
        if s.name == "executor.pass":
            assert {"kind", "tier", "pass_id"} <= set(s.args)
            assert s.args["kind"] in ("decode", "prefill")


def test_staging_runs_on_another_thread_tagged_with_its_pass(traced):
    sess, _, _, spans, main = traced
    pass_ids = {s.args["pass_id"] for s in spans
                if s.name == "executor.pass"}
    stages = [s for s in spans if s.name == "prefetch.stage"]
    assert stages
    for st in stages:
        assert st.line != main
        assert st.args["pass_id"] in pass_ids
        assert st.args["pool"] == "static" and st.args["attempt"] == 0
        copies = _parent_of_kind(st, spans)
        assert len(copies) == 1 and copies[0].args["bytes"] > 0
    at_use = [s for s in spans if s.name == "executor.fetch_at_use"]
    for f in at_use:
        copies = _parent_of_kind(f, spans)
        assert len(copies) == 1
        assert copies[0].args["bytes"] == f.args["bytes"] > 0
    rebinds = [s for s in spans if s.name == "planner.rebind"]
    assert len(rebinds) == 1 and rebinds[0].line == main
    diff = sess.replan_log[0]
    assert rebinds[0].args == {"pinned_bytes": diff.pin_bytes,
                               "evicted_bytes": diff.evict_bytes}


def _parent_of_kind(outer, spans):
    """The ``link.copy`` spans inside ``outer`` on its own thread."""
    return [s for s in spans if s.name == "link.copy"
            and s.line == outer.line and _inside(s, outer)]


def test_tokens_are_the_same_with_the_profiler_on_and_off(traced, db):
    _, _, tokens_on, _, _ = traced
    _, _, tokens_off = serve(db)
    assert tokens_on == tokens_off
    assert all(len(t) == 4 + i for i, t in enumerate(tokens_on))


def test_hbm_bytes_count_the_arrays_each_owner_holds(traced):
    sess, batcher, _, _, _ = traced
    ex = sess.executor
    got = ex.hbm_bytes(kv=batcher.kv)
    assert set(got) == {"pinned", "outputs", "kv", "scratch_peak",
                        "at_use_peak"}
    assert got["pinned"] == sum(x.nbytes for tree in ex._pinned.values()
                                for x in jax.tree.leaves(tree))
    # the host trees they were copied from (the plan's weight_bytes
    # leaves out the biases)
    assert got["pinned"] == sum(tree_nbytes(ex._subtree(pl.sub))
                                for pl in sess.schedule.pinned_placements())
    cfg = sess.cfg
    embed = ex.host["embed"]
    # a tied head is a second, transposed copy of the embedding
    assert cfg.tie_embeddings and ex._unembed_dev.shape == embed.T.shape
    assert got["outputs"] == 2 * embed.nbytes + ex.host["final_norm"].nbytes
    kv_shape = (cfg.n_layers, BATCH, cfg.n_kv_heads, 64,
                cfg.resolved_head_dim)
    assert got["kv"] == 2 * int(np.prod(kv_shape)) * 2
    assert got["kv"] == batcher.kv["k"].nbytes + batcher.kv["v"].nbytes
    largest = max(tree_nbytes(ex._subtree(pl.sub))
                  for t in sess.schedule.tiers.values()
                  for pl in t.plan.placements
                  if pl.sub.kind in ("attn", "ffn"))
    assert 0 < got["scratch_peak"] <= 2 * largest
    assert 0 < got["at_use_peak"] <= largest
    assert ex.prefetch._held_bytes == 0       # every staged tree released
    assert sess.stats()["hbm_bytes"] == got
    assert ex.hbm_bytes()["kv"] == 0
