"""One run of one cell: set-up, a measured window of closed-loop traffic,
the metrics, and the comparison with the reference that decides
``correct``.

Set-up (``setup_s``, from process start to the window) builds the
weights from the seed, opens the session under the cell's HBM budget,
serves one short request per prompt length (every shape the window uses
is compiled then), and runs the loop until ``ramp_requests`` requests
have completed, so the clients are no longer in step when the window
opens. A traced run (``--trace 1``) measures at most ``TRACE_SECONDS``
with the profiler on. After the window the session is closed and the
reference runs over a sample of the requests the window finished.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from chipbench import families, model, traffic
from chipbench.loop import ClosedLoop
from chipbench.peaks import Peaks

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]
METRICS_DIR = CHIP_DIR / "metrics"
TRAFFIC_DIR = CHIP_DIR / "traffic"
CHECKS_DIR = CHIP_DIR / "checks"
SAMPLE_TOKENS = 256        # served tokens the check samples, at least
SAMPLE_MAX = 12            # requests the check samples, at most
CHECK = "mean_logit_gap"
TRACE_SECONDS = 5.0        # longest traced window: traces grow with it


# ---------------------------------------------------------------- cells
@dataclass
class Cell:
    name: str
    chips: int
    raw: dict                      # the configuration file
    spec: traffic.Spec
    end_to_end: List[dict]
    per_layer: List[dict]
    limit: Optional[float]         # of CHECK; None until calibrated

    @property
    def family(self):
        """The module of the configuration's family
        (``chipbench/families/<family>.py``)."""
        return families.load(self.raw["family"])


def load_limit(name: str) -> Optional[float]:
    """The cell's limit on ``CHECK`` from ``checks/<cell>.json``, which
    ``calibrate.py`` writes with the readings it was set from."""
    path = CHECKS_DIR / f"{name}.json"
    if not path.exists():
        return None
    return json.loads(path.read_text())[CHECK]["limit"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    raw = model.load_config(root / configs[w["config"]]["file"])
    spec = traffic.load(TRAFFIC_DIR / f"{w['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return Cell(name, int(w["chips"]), raw, spec,
                mine(bench["end_to_end"]), mine(bench["per_layer"]),
                load_limit(name))


def reader(name: str) -> Callable:
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- window
@dataclass
class Window:
    """What a metric reader reads: the window's steps and requests by the
    host clock, the program's counters over the window, the family's
    counts (``family``, over ``shapes``), and with ``--trace 1`` the
    trace's reduction."""
    start: float
    end: float
    setup_s: float
    steps: list
    sent: list
    counters: dict
    decode_pass_streamed: list
    peak_bytes: Optional[int]
    budget_bytes: int
    shapes: object
    peaks: Optional[Peaks]
    trace: object = None
    family: object = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def within(self, t: float) -> bool:
        return self.start <= t <= self.end

    def step_tokens(self) -> list:
        """Per step of the window, the lengths of the prompts it prefilled
        and, per token it decoded, the cache positions that token attended
        to (itself included), from the tokens' stamps: each token is
        stamped with the end of the step that made it."""
        by_end = {st.t1: ([], []) for st in self.steps}
        for r in self.sent:
            for i, t in enumerate(r.token_at):
                if t in by_end:
                    if i == 0:
                        by_end[t][0].append(r.prompt_len)
                    else:
                        by_end[t][1].append(r.prompt_len + i)
        return list(by_end.values())


def counters(ex) -> dict:
    st = ex.stats
    out = {"decode_passes": st.decode_passes,
           "prefill_passes": st.prefill_passes,
           "streamed_bytes": st.streamed_bytes,
           "at_use_bytes": st.at_use_bytes,
           "copy_s_exposed": st.copy_s_exposed,
           "prefetch_bytes": 0, "prefetch_copy_s": 0.0}
    if ex.prefetch is not None:
        ps = ex.prefetch.stats
        out["prefetch_bytes"] = ps.staged_bytes
        out["prefetch_copy_s"] = ps.copy_s_hidden + ps.copy_s_exposed
    return out


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


# ---------------------------------------------------------------- compile
class CompileMeter:
    """Seconds JAX spent compiling and how many programs it compiled or
    read from the persistent cache, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------- run
@dataclass
class Run:
    result: dict
    check_lines: List[str] = field(default_factory=list)


def judge(gap: Optional[float], limit: Optional[float],
          failed: int) -> bool:
    """``correct``: a reading of ``CHECK`` within the cell's limit, and no
    request failed. No reading or no limit is not correct."""
    return (gap is not None and limit is not None and gap <= limit
            and failed == 0)


def open_session(cell: Cell, cfg, params, system):
    from repro import Session
    from repro.core import InferenceSetting, run_install
    spec = cell.spec
    budget = int(spec.hbm_budget_x * model.weight_bytes(cfg))
    setting = InferenceSetting(batch=spec.clients, context=spec.max_seq,
                               max_new_tokens=spec.output_max)
    return Session.open(cfg, system, budget, setting,
                        db=run_install(system, quick=True),
                        max_seq=spec.max_seq, params=params)


def warm_up(batcher, requests):
    from repro.core.serving import Request
    batcher.submit([Request(rid=r.index, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens)
                    for r in requests])
    while batcher.has_work:
        batcher.step()


def sample_for_check(finished, seed: int) -> list:
    """The longest finished request, then others drawn from the seed,
    until the sample holds ``SAMPLE_TOKENS`` served tokens."""
    if not finished:
        return []
    rng = np.random.default_rng(seed ^ 0xC0FFEE)
    longest = max(finished, key=lambda r: r.prompt_len + len(r.served))
    rest = [r for r in finished if r is not longest]
    order = rng.permutation(len(rest))
    out = [longest]
    tokens = len(longest.served)
    for i in order:
        if tokens >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        tokens += len(rest[i].served)
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, system, device, peaks: Optional[Peaks],
             log_dir: Optional[str] = None,
             fault: Optional[Callable] = None,
             controls: tuple = (),
             clock: Callable = time.perf_counter) -> Run:
    """``fault(session)`` breaks the timed path and ``clock`` replaces
    the loop's clock (the harness's own tests); ``controls`` names the low
    precisions (``reference.LOW``) whose readings are also taken over the
    same sample: the reference computed in that precision, the gap of the
    token it puts first (calibration)."""
    import jax
    from chipbench import reference
    meter = CompileMeter()
    meter.install()
    raw, spec, fam = cell.raw, cell.spec, cell.family
    cfg = fam.model_config(raw)
    params = fam.make_weights(raw, seed)
    requests = traffic.generate(spec, cfg.vocab, seed)
    sess = open_session(cell, cfg, params, system)
    batcher = sess.batcher(max_batch=spec.clients)
    if fault is not None:
        fault(sess)
    warm_up(batcher, traffic.warmup(spec, cfg.vocab))
    loop = ClosedLoop(batcher, requests, spec.clients, annotate=trace,
                      clock=clock)
    loop.start()
    loop.run_until_completed(spec.ramp_requests)
    jax.block_until_ready(batcher.kv)
    setup_s = time.perf_counter() - t_process
    ex = sess.executor
    c0, n0 = counters(ex), len(ex.stats.pass_streamed_bytes)
    compiles0, compile_s0 = meter.compiles, meter.seconds
    steps0 = len(loop.steps)
    summary = None
    if trace:
        from jax.profiler import ProfileOptions, TraceAnnotation
        from chipbench import trace as tr
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with TraceAnnotation(tr.WINDOW):
            start, end = loop.run_for(min(seconds, TRACE_SECONDS))
        jax.profiler.stop_trace()
    else:
        start, end = loop.run_for(seconds)
    c1 = counters(ex)
    compiles = meter.compiles - compiles0
    compile_s = meter.seconds - compile_s0
    if trace:
        summary = tr.reduce_trace(tr.find_xplane(log_dir))
    mem = device.memory_stats() if device is not None else None
    peak = (mem or {}).get("peak_bytes_in_use")
    w = Window(start=start, end=end, setup_s=setup_s,
               steps=loop.steps[steps0:], sent=list(loop.sent),
               counters=delta(c0, c1),
               decode_pass_streamed=list(ex.stats.pass_streamed_bytes[n0:]),
               peak_bytes=peak, budget_bytes=sess.budget_bytes,
               shapes=fam.shapes_of(cfg), peaks=peaks, trace=summary,
               family=fam)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = reader(m["name"])(w)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    done = [r for r in loop.sent
            if r.done_at is not None and w.within(r.done_at)]
    finished = [r for r in done if r.error is None]
    failed = len(done) - len(finished)
    sample = sample_for_check(finished, seed)
    seqs = [(r.request.prompt, r.served) for r in sample]
    plan = plan_summary(sess, spec)
    n_steps = len(w.steps)
    del loop, batcher, ex, w, requests
    sess.close()
    del sess
    gc.collect()
    gap = widest = off = None
    control_gaps = {}
    t_ref = time.perf_counter()
    if seqs:
        size = dict(length=spec.max_seq, rows=spec.output_max)
        ref = fam.logits_at(raw, params, seqs, **size)
        gaps = np.concatenate([reference.gaps(r, s)
                               for r, (_, s) in zip(ref, seqs)])
        gap, widest = float(gaps.mean()), float(gaps.max())
        off = int((gaps > 0).sum())
        for kind in controls:
            low = fam.logits_at(raw, params, seqs, low=kind, **size)
            gaps = np.concatenate([reference.gaps(r, c.argmax(-1))
                                   for r, c in zip(ref, low)])
            control_gaps[kind] = {CHECK: float(gaps.mean()),
                                  "max_logit_gap": float(gaps.max()),
                                  "tokens_off_best": int((gaps > 0).sum())}
    reference_s = time.perf_counter() - t_ref
    limit = cell.limit
    correct = judge(gap, limit, failed)
    dev = {"platform": device.platform if device else "none",
           "kind": device.device_kind if device else "none",
           "count": len(jax.devices()),
           "memory_peak_bytes": int(peak or 0)}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
    result = {"correct": correct, "attempted": len(done), "failed": failed,
              "metrics": metrics, "device": dev}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_modules(),
                               "idle_gaps": summary.top_gaps()}
    result["window"] = {"seconds": end - start, "steps": n_steps,
                        "compiles": compiles, "compile_s": compile_s,
                        "setup_compile_s": compile_s0,
                        "setup_cache_hits": meter.cache_hits,
                        "sampled_requests": len(seqs),
                        "sampled_tokens": sum(len(s) for _, s in seqs),
                        "max_logit_gap": widest, "tokens_off_best": off,
                        "reference_s": reference_s, "plan": plan}
    if controls:
        result["window"]["controls"] = control_gaps
    result["check"] = {CHECK: {"value": gap, "limit": limit},
                       "failed_requests": {"value": failed, "limit": 0}}
    lines = [f"check {CHECK} {gap!r} limit {limit!r}",
             f"check failed_requests {failed} limit 0"]
    return Run(result, lines)


def plan_summary(sess, spec) -> dict:
    """The decode plan at full batch: tier, pinned, streamed and at-use
    bytes per pass."""
    sched = sess.schedule
    tier = sched.pick_decode_tier(spec.clients)
    plan = sched.tiers[tier].plan
    return {"budget_bytes": sess.budget_bytes, "decode_tier": tier,
            "plan": plan.name, "pinned_bytes": sched.pinned_bytes,
            "streamed_bytes": sum(p.sub.weight_bytes
                                  for p in plan.placements
                                  if p.streamed and p.engine == "gpu"),
            "at_use_bytes": sum(p.sub.weight_bytes
                                for p in plan.placements
                                if p.engine == "cpu")}
