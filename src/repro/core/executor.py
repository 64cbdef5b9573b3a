"""Inference-phase executor (paper Step 3/4): run a planned schedule with
true pipelined copy-compute.

Executes a transformer-family model *sub-layer by sub-layer* following the
Schedule's per-tier plan: pinned sub-layers use pre-placed ("VRAM") arrays;
streamed ones are staged by a background ``PrefetchEngine`` into a two-slot
scratch double-buffer one sub-layer ahead of compute, so sub-layer i+1's
host->device copy hides under sub-layer i's compute; CPU-assigned ones are
fetched synchronously at use (the slow-tier simulation on this container).
Realized overlap (hidden vs exposed copy time) is recorded in ``ExecStats``.

Compute runs through the jitted ``SubLayerEngine``: one compiled step
function per sub-layer kind, shared across layers, chunks and decode steps;
KV caches are stacked ``(n_layers, B, KV, S, hd)`` arrays so the decode loop
never rebuilds host trees. ``overlap=False`` falls back to synchronous
at-use transfers and ``jit_engine=False`` to the seed's eager per-sub-layer
dispatch — both kept as baselines for the bit-identity tests and the
overlap benchmark.

Chunked prefill: the picked tier is the chunk size (paper: "T serves as the
optimal chunk size for chunked prefills").
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.costmodel import Placement
from repro.core.engine import SubLayerEngine
from repro.core.faults import (DemandTimeout, FaultPlan, RecoveryPolicy,
                               WorkerLost)
from repro.core.kvpaged import NULL_PAGE, PAGE_SIZE, PagedKVCache
from repro.core.planner import Schedule
from repro.core.prefetch import PrefetchEngine, copy_to_device, tree_nbytes
from repro.core.sublayer import SubLayer
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models.common import NoPolicy, greedy_token, rmsnorm


@dataclass
class ExecStats:
    streamed_bytes: int = 0      # plan-accounted streamed weight bytes
    # same bytes split by the shard's storage format ("fp16"/"int8"/"int4",
    # from SubLayer.meta["quant"]) — the DESIGN.md §11 repricing surface
    streamed_bytes_by_dtype: dict = field(default_factory=dict)
    at_use_bytes: int = 0        # non-streamed (CPU-engine) at-use fetches
    at_use_peak_bytes: int = 0   # largest tree fetched at use
    staged_bytes: int = 0        # actual host->device bytes moved
    copy_s_hidden: float = 0.0   # streamed copy time hidden under compute
    copy_s_exposed: float = 0.0  # streamed copy time compute waited on
    prefetch_slots: int = 0      # realised scratch double-buffer depth
    boundary_hops: int = 0
    engine_calls: dict = field(default_factory=lambda: {"gpu": 0, "cpu": 0})
    tiers_used: list = field(default_factory=list)
    # per _run_decode pass: one pass == one serving iteration in fused mode,
    # one pass per active slot in the per-slot baseline
    decode_passes: int = 0
    pass_streamed_bytes: list = field(default_factory=list)
    # prefill loop-order accounting (DESIGN.md §10): layer-major runs ONE
    # plan pass per prompt (each streamed sub-layer crosses the link once),
    # chunk-major one pass per chunk (C x the streamed plan bytes). Each
    # prefill() call appends a dict with its mode, chunk count, passes,
    # streamed/demanded bytes and hidden-vs-exposed copy seconds.
    prefill_passes: int = 0
    prefill_stats: list = field(default_factory=list)
    # expert-granular MoE accounting (DESIGN.md §9): how many expert shards
    # the routers demanded, how many of those were already pinned (hits),
    # and the demanded-vs-resident byte split. streamed_bytes ==
    # plan-static streamed bytes + demanded_expert_bytes, always.
    expert_demanded: int = 0
    expert_hits: int = 0
    demanded_expert_bytes: int = 0
    resident_expert_bytes: int = 0       # pinned expert bytes right now
    pass_expert_stats: list = field(default_factory=list)
    # paged-KV block restores (DESIGN.md §12): the second demand-streamable
    # shard kind beside cold experts. The ledger generalises to
    # streamed_bytes == static plan + demanded_expert_bytes +
    # demanded_page_bytes, always ("kv" bucket in streamed_bytes_by_dtype).
    page_faults: int = 0
    demanded_page_bytes: int = 0
    # speculative decoding (DESIGN.md §14): drafted = draft tokens offered
    # to verify passes, accepted = drafted tokens the target confirmed
    # (bonus tokens from the target's own argmax are NOT counted — the
    # ratio is the draft-model acceptance rate the planner's k-choice
    # models), rollbacks = slots whose rejected KV suffix was rolled back.
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_rollbacks: int = 0
    spec_rolled_back_tokens: int = 0
    spec_verify_passes: int = 0
    # per verify pass: streamed/static/expert/page byte split for the
    # hard-ledger assertion streamed == static + experts + pages
    verify_pass_stats: list = field(default_factory=list)
    # fault recovery (DESIGN.md §15): retries/failures mirror the prefetch
    # engine's counters; sync_fallbacks are shards the pass fetched itself
    # after a stage failure or demand deadline; degraded_sync flips when
    # the worker watchdog parks the executor on the overlap=False path
    fault_copy_retries: int = 0
    fault_copy_failures: int = 0
    fault_worker_crashes: int = 0
    fault_demand_timeouts: int = 0
    fault_sync_fallbacks: int = 0
    fault_alloc_failures: int = 0
    degraded_sync: bool = False

    @property
    def accept_rate(self) -> float:
        return self.spec_accepted / max(self.spec_drafted, 1)

    @property
    def expert_hit_rate(self) -> float:
        return self.expert_hits / max(self.expert_demanded, 1)
    # live re-plan swaps (rebind, DESIGN.md §8): only the pin/evict deltas
    # between the old and new schedules are moved — these fields must match
    # Schedule.diff byte for byte
    rebinds: int = 0
    rebind_pinned_bytes: int = 0
    rebind_evicted_bytes: int = 0
    rebind_s: float = 0.0


def resolve_prefill_mode(prefill_mode, jit_engine: bool) -> str:
    """``None`` -> the engine default (layer-major needs the jitted
    engine's ``*_prefill_step`` variants, DESIGN.md §10). Shared by
    ``PipelinedExecutor`` and ``Session.effective_prefill_mode`` so the
    resolution rule cannot drift between the runner and the estimator."""
    if prefill_mode is None:
        return "layer_major" if jit_engine else "chunk_major"
    return prefill_mode


class PipelinedExecutor:
    """Dense/MoE decoder executor under a pipelined-sharding schedule."""

    def __init__(self, cfg, params, schedule: Schedule, max_seq: int = 512,
                 overlap: bool = True, jit_engine: bool = True,
                 prefill_mode: str | None = None,
                 kv_layout: str = "stacked",
                 kv_page_size: int | None = None,
                 kv_pool_pages: int | None = None,
                 faults: FaultPlan | None = None,
                 recovery: RecoveryPolicy | None = None):
        assert cfg.family in ("dense", "moe"), \
            "executor demo covers the dense/moe families"
        self.cfg = cfg
        self.schedule = schedule
        self.max_seq = max_seq
        # paged KV (DESIGN.md §12) needs the jitted engine's paged
        # gather/scatter steps; an explicit "paged" that cannot be honoured
        # raises (same contract as expert_granular / prefill_mode)
        if kv_layout not in ("stacked", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_layout == "paged" and not jit_engine:
            raise ValueError("kv_layout='paged' requires the jitted engine "
                             "(jit_engine=True)")
        self.kv_layout = kv_layout
        self.kv_page_size = kv_page_size or PAGE_SIZE
        self.kv_pool_pages = kv_pool_pages   # usable pages; None -> ample
        self._active_kvcache = None          # paged cache of the live pass
        # layer-major weight-stationary prefill (DESIGN.md §10) needs the
        # jitted engine's *_prefill_step variants; the eager baseline keeps
        # the seed's chunk-major loop. An explicit "layer_major" that
        # cannot be honoured raises (same contract as expert_granular).
        prefill_mode = resolve_prefill_mode(prefill_mode, jit_engine)
        if prefill_mode not in ("layer_major", "chunk_major"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if prefill_mode == "layer_major" and not jit_engine:
            raise ValueError("prefill_mode='layer_major' requires the "
                             "jitted engine (jit_engine=True)")
        self.prefill_mode = prefill_mode
        # live queue-pressure hints (DESIGN.md §13): the serving layer sets
        # these before a pass so the tier picks anticipate the imminent
        # batch (admission bursts) and respect deadline slack; the defaults
        # keep every pick identical to the queue-blind baseline
        self.sched_queue_depth = 0
        self.sched_slack_s: float | None = None
        self.policy = NoPolicy()
        self.stats = ExecStats()
        # fault injection + recovery (DESIGN.md §15): `faults` is the
        # opt-in chaos plan (None == every check compiles to a no-op
        # branch); `recovery` is always on
        self.faults = faults
        self.recovery = recovery if recovery is not None else RecoveryPolicy()
        self._sync_exposed = 0.0
        self._sync_staged = 0
        self._pass_id = 0            # tags each pass's spans
        # split params into per-sublayer host copies ("sysRAM")
        self.host = {"embed": np.asarray(params["embed"]),
                     "final_norm": np.asarray(params["final_norm"])}
        if "unembed" in params:
            self.host["unembed"] = np.asarray(params["unembed"])
        self.layer_params = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda x: np.asarray(x[i]), params["layers"])
            self.layer_params.append(lp)
        # embed / final norm / output head live once on device (the paper
        # pins outputs last; at smoke scale they always fit)
        self._embed_dev = jnp.asarray(self.host["embed"])
        self._final_dev = jnp.asarray(self.host["final_norm"])
        self._unembed_dev = (self._embed_dev.T if cfg.tie_embeddings
                             else jnp.asarray(self.host["unembed"]))
        # pin once per schedule (paper pins identically across tiers); the
        # canonical pin set comes from the schedule itself so rebind() and
        # Schedule.diff stay in exact agreement (DESIGN.md §8)
        self._pinned = {}
        self._pinned_bytes = {}
        self._pinned_kinds = {}
        for pl in schedule.pinned_placements():
            self._pinned[pl.sub.name] = jax.device_put(self._subtree(pl.sub))
            self._pinned_bytes[pl.sub.name] = pl.sub.weight_bytes
            self._pinned_kinds[pl.sub.name] = pl.sub.kind
        self._pinned_names = set(self._pinned)
        self.engine = SubLayerEngine(cfg, self.policy) if jit_engine else None
        self.prefetch = PrefetchEngine(self._subtree, faults=faults,
                                       recovery=self.recovery) \
            if overlap else None
        self._layer_ids = [jnp.asarray(i, jnp.int32)
                           for i in range(cfg.n_layers)]
        # expert-granular MoE (DESIGN.md §9): the schedule's graph splits
        # each moe sub-layer into router + per-expert shards; the engine's
        # phased moe step demand-streams the router-selected cold experts
        self.expert_granular = schedule.expert_granular
        assert not self.expert_granular or self.engine is not None, \
            "expert-granular schedules require the jitted engine " \
            "(jit_engine=True)"
        self._stack_cache: dict = {}       # layer -> (stack dict, mask dev)
        self._zeros_cache: dict = {}       # key -> zeroed (E, ...) template
        self.expert_ema: dict = {}         # layer -> np (E,) routing freqs
        self.ema_alpha = 0.25
        self._refresh_resident_expert_bytes()
        if self.expert_granular:
            # warm the fold executable now: its first real use is gated on
            # an expert being COLD, so without this an ample-budget serve
            # would hit a fresh compile the moment a rebind evicts its
            # first expert — mid-serve, violating §8's no-retrace
            # invariant (expert shapes match across layers, one executable
            # covers all)
            keys = self._expert_keys(0)
            moe = self.layer_params[0]["moe"]
            self.engine.fold_expert_step(
                {k: self._expert_zeros(k, moe[k][0]) for k in keys},
                {k: jnp.zeros(moe[k][0].shape, moe[k][0].dtype)
                 for k in keys},
                jnp.asarray(0, jnp.int32))

    def hbm_bytes(self, kv=None) -> dict:
        """Device bytes held by each owner, counted from the arrays: the
        plan's pinned sub-layers; the outputs the executor holds outside
        the plan (embedding, final norm and head, where a tied head is a
        transposed copy of the embedding); the KV cache ``kv`` the caller
        serves from; the prefetcher's high-water of staged bytes not yet
        released; and the largest tree fetched at use."""
        if kv is None:
            kv_arrays = []
        elif isinstance(kv, PagedKVCache):
            kv_arrays = [kv.k_pool, kv.v_pool]
        else:
            kv_arrays = jax.tree.leaves(kv)
        return {
            "pinned": sum(x.nbytes for tree in self._pinned.values()
                          for x in jax.tree.leaves(tree)),
            "outputs": sum(a.nbytes for a in (self._embed_dev,
                                              self._final_dev,
                                              self._unembed_dev)),
            "kv": sum(x.nbytes for x in kv_arrays),
            "scratch_peak": (self.prefetch.stats.scratch_peak_bytes
                             if self.prefetch is not None else 0),
            "at_use_peak": self.stats.at_use_peak_bytes,
        }

    def _refresh_resident_expert_bytes(self):
        self.stats.resident_expert_bytes = sum(
            self._pinned_bytes[n] for n, k in self._pinned_kinds.items()
            if k == "moe_expert")

    # ------------------------------------------------------------ rebind
    def rebind(self, schedule: Schedule) -> dict:
        """Swap in a re-planned schedule live (DESIGN.md §8).

        Applies only the pin/evict delta between the bound and the new
        schedule: sub-layers leaving the pinned set drop their device
        arrays, entering ones are ``device_put`` once — the unchanged
        intersection is never touched, KV caches (owned by the caller) and
        the jitted engine executables survive, so in-flight decode slots
        keep their state and no step re-traces. Must be called between
        passes (never while a prefetch session is staging).

        Returns a report dict whose ``pinned_bytes``/``evicted_bytes``
        equal the corresponding ``Schedule.diff`` fields.
        """
        assert self.prefetch is None or not self.prefetch.active, \
            "rebind during an active prefetch session (mid-pass)"
        t0 = time.perf_counter()
        new_pins = {pl.sub.name: pl for pl in schedule.pinned_placements()}
        to_evict = [n for n in self._pinned if n not in new_pins]
        to_pin = [n for n in new_pins if n not in self._pinned]
        evicted_bytes = sum(self._pinned_bytes[n] for n in to_evict)
        pinned_bytes = sum(new_pins[n].sub.weight_bytes for n in to_pin)
        with TraceAnnotation("planner.rebind", pinned_bytes=pinned_bytes,
                             evicted_bytes=evicted_bytes):
            for name in to_evict:
                del self._pinned[name]
                del self._pinned_kinds[name]
                del self._pinned_bytes[name]
            staged = []
            for name in to_pin:
                pl = new_pins[name]
                tree = jax.device_put(self._subtree(pl.sub))
                staged.append(tree)
                self._pinned[name] = tree
                self._pinned_bytes[name] = pl.sub.weight_bytes
                self._pinned_kinds[name] = pl.sub.kind
            for tree in staged:
                jax.block_until_ready(tree)
        self.schedule = schedule
        self._pinned_names = set(self._pinned)
        # per-layer pinned-expert weight stacks are views of the pin set:
        # rebuild them lazily against the new residency (DESIGN.md §9)
        self._stack_cache.clear()
        self._refresh_resident_expert_bytes()
        dt = time.perf_counter() - t0
        self.stats.rebinds += 1
        self.stats.rebind_pinned_bytes += pinned_bytes
        self.stats.rebind_evicted_bytes += evicted_bytes
        self.stats.rebind_s += dt
        return {"to_pin": to_pin, "to_evict": to_evict,
                "pinned_bytes": pinned_bytes,
                "evicted_bytes": evicted_bytes, "seconds": dt}

    # ------------------------------------------------------------ weights
    # weight-matrix keys of one expert's stack (+ scales / int4 zero-points
    # when quantised)
    _EXPERT_KEYS = ("w_gate", "w_up", "w_down")
    _SCALE_KEYS = ("s_gate", "s_up", "s_down")
    _ZERO_KEYS = ("z_gate", "z_up", "z_down")

    def _account_streamed(self, placement):
        """Single accounting point for plan-priced streamed bytes, bucketed
        by the shard's storage format (DESIGN.md §11)."""
        wb = placement.sub.weight_bytes
        q = placement.sub.meta.get("quant", "fp16")
        self.stats.streamed_bytes += wb
        self.stats.streamed_bytes_by_dtype[q] = \
            self.stats.streamed_bytes_by_dtype.get(q, 0) + wb

    def _subtree(self, sub):
        lp = self.layer_params[sub.layer]
        if sub.kind == "attn":
            return {"attn": lp["attn"], "ln1": lp["ln1"]}
        if sub.kind in ("ffn", "moe"):
            key = "moe" if "moe" in lp else "ffn"
            return {key: lp[key], "ln2": lp["ln2"]}
        if sub.kind == "moe_router":
            return {"router": lp["moe"]["router"], "ln2": lp["ln2"]}
        if sub.kind == "moe_expert":
            e = sub.meta["expert"]
            moe = lp["moe"]
            keys = [k for k in
                    self._EXPERT_KEYS + self._SCALE_KEYS + self._ZERO_KEYS
                    if k in moe]
            return {k: moe[k][e] for k in keys}
        if sub.kind == "kv_page":
            # paged-KV block restore (DESIGN.md §12): the "weights" are the
            # faulted block's host-evicted page data. Resolved against the
            # pass's live cache — also from the prefetch worker thread.
            cache = self._active_kvcache
            assert cache is not None, "kv_page fetch outside a paged pass"
            return cache.host_tree(sub.meta["bid"])
        raise ValueError(sub.kind)

    def _copy_at_use(self, sub, tree, nbytes: int):
        """The consumer's own transfer of ``sub``'s host ``tree``, waited
        on: the ``executor.fetch_at_use`` span."""
        with TraceAnnotation("executor.fetch_at_use", sub=sub.name,
                             bytes=nbytes):
            dev = copy_to_device(tree, nbytes)
        self._sync_staged += nbytes
        self.stats.at_use_peak_bytes = max(self.stats.at_use_peak_bytes,
                                           nbytes)
        return dev

    def _fetch_sync(self, placement):
        """Synchronous at-use transfer (CPU-engine placements, and every
        streamed placement when overlap is disabled)."""
        tree = self._subtree(placement.sub)
        nbytes = tree_nbytes(tree)
        t0 = time.perf_counter()
        dev = self._copy_at_use(placement.sub, tree, nbytes)
        dt = time.perf_counter() - t0
        if placement.streamed and placement.engine == "gpu":
            self._account_streamed(placement)
            self._sync_exposed += dt
        else:
            self.stats.at_use_bytes += nbytes
        return dev

    def _weights_for(self, placement, streaming: set):
        """Returns (device tree, needs_release)."""
        name = placement.sub.name
        if name in self._pinned_names:
            return self._pinned[name], False
        if name in streaming:
            # accounting happens BEFORE the acquire, so the recovery
            # fallback below must move the bytes WITHOUT re-accounting
            self._account_streamed(placement)
            try:
                return self.prefetch.acquire(name), True
            except Exception as e:
                self._note_stream_fault(e)
                # drop the failed entry NOW — discard frees its scratch
                # slot iff the worker held one, so the rest of the pass's
                # staging never wedges behind a dead slot
                self.prefetch.discard(name)
                return self._raw_fetch(placement.sub), False
        return self._fetch_sync(placement), False

    def _raw_fetch(self, sub):
        """Recovery transfer with NO ledger accounting — used where the
        plan-priced bytes were already (or will be) accounted by the
        caller, so a retried shard lands in the ledger exactly once."""
        host = self._subtree(sub)
        return self._copy_at_use(sub, host, tree_nbytes(host))

    # ------------------------------------------------------------ recovery
    def _note_stream_fault(self, exc: Exception):
        """Count one sync-fetch recovery and trip the worker watchdog when
        the crash budget is spent (DESIGN.md §15)."""
        self.stats.fault_sync_fallbacks += 1
        if isinstance(exc, DemandTimeout):
            self.stats.fault_demand_timeouts += 1
        if isinstance(exc, WorkerLost):
            crashes = self.prefetch.stats.worker_crashes
            if not self.stats.degraded_sync and \
                    crashes >= self.recovery.crash_tolerance:
                # worker watchdog: every later acquire of the dead pool
                # would fail too — park the executor on the overlap=False
                # sync path (bit-identical) from the next pass on
                self.stats.degraded_sync = True

    def _demand_timeout_s(self):
        return self.recovery.demand_deadline_s

    def _demand_acquire(self, pl):
        """Acquire a demand-streamed shard under the per-demand deadline
        (DESIGN.md §15). Returns ``(tree, needs_release)`` — on a timeout
        the entry is abandoned (its slot frees when the copy lands), on a
        stage failure it is discarded (slot freed iff the worker held
        one); either way the shard is sync-fetched so the pass NEVER
        deadlocks on a demand. The caller accounts the bytes exactly
        once, after this returns."""
        name = pl.sub.name
        try:
            if self.faults is not None:
                self.faults.check("demand.timeout", key=name)
            return self.prefetch.acquire(
                name, timeout=self._demand_timeout_s()), True
        except Exception as e:
            if isinstance(e, DemandTimeout):
                self.prefetch.abandon(name)
            else:
                self.prefetch.discard(name)
            self._note_stream_fault(e)
            return self._raw_fetch(pl.sub), False

    def _check_alloc(self, where: str):
        """Device-allocation injection point at a pass entry — BEFORE any
        KV mutation, so the serving layer can degrade one ladder rung and
        re-run the pass cleanly (DESIGN.md §15)."""
        if self.faults is not None:
            try:
                self.faults.check("alloc.device", key=where)
            except Exception:
                self.stats.fault_alloc_failures += 1
                raise

    def _sync_stats(self):
        self.stats.copy_s_exposed = self._sync_exposed
        self.stats.staged_bytes = self._sync_staged
        self.stats.copy_s_hidden = 0.0
        if self.prefetch is not None:
            ps = self.prefetch.stats
            self.stats.copy_s_hidden = ps.copy_s_hidden
            self.stats.copy_s_exposed += ps.copy_s_exposed
            self.stats.staged_bytes += ps.staged_bytes
            self.stats.prefetch_slots = ps.slots
            self.stats.fault_copy_retries = ps.copy_retries
            self.stats.fault_copy_failures = ps.copy_failures
            self.stats.fault_worker_crashes = ps.worker_crashes

    # ------------------------------------------------------------ sub-layers
    def _attn_sub(self, w, x, k, v, i, pos_arr, pos):
        if self.engine is not None:
            return self.engine.attn_step(w, x, k, v, self._layer_ids[i],
                                         pos_arr)
        # seed path: eager per-sub-layer dispatch through the same shared
        # attention_block as the jitted engine — only compilation differs
        cfg = self.cfg
        B, T, _ = x.shape
        positions = (pos + jnp.arange(T)[None, :]) * jnp.ones((B, 1),
                                                              jnp.int32)
        h = rmsnorm(x, w["ln1"], cfg.norm_eps)
        out, cache = attn_mod.attention_block(
            w["attn"], cfg, h, positions, self.policy,
            cache={"k": k[i], "v": v[i]}, cache_pos=pos)
        # eager path carries per-layer lists (like the seed executor did) so
        # the baseline is not charged a full-stack copy per layer
        k[i], v[i] = cache["k"], cache["v"]
        return x + out, k, v

    def _ffn_sub(self, w, x, streamed: bool):
        if self.engine is not None:
            if self.cfg.moe is not None:
                return self.engine.moe_step(w, x)
            return self.engine.ffn_step(w, x, streamed=streamed)
        cfg = self.cfg
        h = rmsnorm(x, w["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h = mlp_mod.moe_ffn(w["moe"], cfg, h, self.policy)
        else:
            h = mlp_mod.ffn(w["ffn"], cfg, h, self.policy)
        return x + h

    # ------------------------------------------------ expert-granular moe
    def _expert_zeros(self, key, spec):
        """Cached zero-filled (E, ...) stack template for one weight key;
        absent experts contribute zero rows the combine never gathers."""
        cache_key = (key, spec.shape, str(spec.dtype))
        z = self._zeros_cache.get(cache_key)
        if z is None:
            z = jnp.zeros((self.cfg.moe.n_experts,) + spec.shape, spec.dtype)
            self._zeros_cache[cache_key] = z
        return z

    def _expert_keys(self, layer):
        moe = self.layer_params[layer]["moe"]
        return [k for k in
                self._EXPERT_KEYS + self._SCALE_KEYS + self._ZERO_KEYS
                if k in moe]

    def _pinned_expert_stack(self, layer):
        """(stacked weights, membership mask) of the experts currently
        pinned for ``layer``. Cached between rebinds — the pinned group is
        static while the schedule is, so the hot-expert phase never pays a
        host->device copy (DESIGN.md §9).

        Single-device simulation concession: the group stacks are
        full-(E, ...) zero-padded buffers so both expert phases share one
        shape-stable executable — on this container "device" and "host"
        are the same memory, so the zero padding costs address space, not
        the VRAM the planner budgets. The paper-fidelity surfaces are the
        plan's per-expert pin accounting and the HOST->DEVICE transfer
        counters, which stay expert-granular; a real deployment would back
        this with a paged per-expert buffer instead."""
        cached = self._stack_cache.get(layer)
        if cached is not None:
            return cached
        E = self.cfg.moe.n_experts
        moe = self.layer_params[layer]["moe"]
        keys = self._expert_keys(layer)
        stack = {k: self._expert_zeros(k, moe[k][0]) for k in keys}
        mask = np.zeros((E,), bool)
        for e in range(E):
            tree = self._pinned.get(f"L{layer}/moe.expert{e}")
            if tree is None:
                continue
            mask[e] = True
            for k in keys:
                stack[k] = stack[k].at[e].set(tree[k])
        cached = (stack, jnp.asarray(mask))
        self._stack_cache[layer] = cached
        return cached

    def _record_routing(self, layer, idx_host):
        """EMA of router selection frequencies — the online refinement of
        the profile-DB routing stats the planner pins hot experts from
        (DESIGN.md §9)."""
        E = self.cfg.moe.n_experts
        counts = np.bincount(idx_host.reshape(-1),
                             minlength=E).astype(np.float64)
        freq = counts / max(counts.sum(), 1.0)
        prev = self.expert_ema.get(layer)
        self.expert_ema[layer] = freq if prev is None else \
            (1 - self.ema_alpha) * prev + self.ema_alpha * freq

    def _moe_sub_granular(self, layer, x, by_name, streaming):
        """One expert-granular MoE sub-layer (DESIGN.md §9):

        route first (router is priority-pinned, so this never waits on the
        link), sync the selected expert ids to the host, and request ONLY
        the demanded cold experts from the prefetcher's demand pool; the
        pinned-expert phase computes while those copies are in flight;
        the streamed-expert phase folds each demanded shard into a
        zero-filled stack as it lands (the fold copies the data, so the
        scratch slot frees immediately); a where-merge by pinned
        membership then reproduces the monolithic path's expert buffer
        bit for bit.
        """
        eng = self.engine
        r_pl = by_name[f"L{layer}/moe.router"]
        w_r, rel_r = self._weights_for(r_pl, streaming)
        self.stats.engine_calls[r_pl.engine] += 1
        disp, aux, idx = eng.moe_route_step(w_r, x)
        if rel_r:
            self.prefetch.release(r_pl.sub.name)
        idx_host = np.asarray(idx)          # host sync: the demanded set
        self._record_routing(layer, idx_host)
        cold, streamed_cold = self._demand_cold_experts(
            layer, np.unique(idx_host), by_name)
        stack_pinned, mask = self._pinned_expert_stack(layer)
        buf_p = eng.moe_experts_step(stack_pinned, disp)
        if cold:
            stream_stack = self._fold_cold_experts(layer, cold,
                                                   streamed_cold)
            buf_s = eng.moe_experts_step(stream_stack, disp)
        else:
            # nothing demanded was cold: the streamed buffer is never
            # selected by the mask, reuse the pinned one
            buf_s = buf_p
        return eng.moe_combine_step(x, buf_p, buf_s, mask, aux)

    def _demand_cold_experts(self, layer, demanded, by_name):
        """Split the demanded expert ids of ``layer`` into pinned hits and
        cold shards, account the hit stats, and enqueue the streamable
        cold shards on the demand pool BEFORE the pinned phase runs — so
        their copies hide under the resident experts' compute. Shared by
        the per-chunk decode path and the layer-major union path.
        Returns ``(cold, streamed_cold)`` placement lists."""
        cold = []
        for e in demanded:
            name = f"L{layer}/moe.expert{int(e)}"
            if name in self._pinned_names:
                self.stats.expert_hits += 1
            else:
                cold.append(by_name[name])
        self.stats.expert_demanded += len(demanded)
        streamed_cold = [pl for pl in cold if self._demand_active
                         and pl.streamed and pl.engine == "gpu"]
        if streamed_cold:
            self.prefetch.request(streamed_cold)
        return cold, streamed_cold

    def _fold_cold_experts(self, layer, cold, streamed_cold):
        """Acquire every demanded cold expert shard of ``layer`` and fold
        it into a zero-filled (E, ...) group stack. Fold-then-release: the
        fold copies the shard into the stack, so each scratch slot frees
        before the next acquire even under a single demand slot."""
        eng = self.engine
        keys = self._expert_keys(layer)
        moe = self.layer_params[layer]["moe"]
        stream_stack = {k: self._expert_zeros(k, moe[k][0]) for k in keys}
        requested = {pl.sub.name for pl in streamed_cold}
        for pl in cold:
            name = pl.sub.name
            self.stats.engine_calls[pl.engine] += 1
            if name in requested:
                # demand acquire under deadline; recovery sync-fetches on
                # a miss — in either branch the plan-priced bytes are
                # accounted exactly once, right here (DESIGN.md §15)
                tree, rel = self._demand_acquire(pl)
                self._account_streamed(pl)
                self.stats.demanded_expert_bytes += pl.sub.weight_bytes
            else:
                # at-use transfer (overlap disabled, or a CPU-engine
                # placement); _fetch_sync accounts streamed/at-use
                tree = self._fetch_sync(pl)
                rel = False
                if pl.streamed and pl.engine == "gpu":
                    self.stats.demanded_expert_bytes += pl.sub.weight_bytes
            stream_stack = eng.fold_expert_step(
                stream_stack, tree,
                jnp.asarray(pl.sub.meta["expert"], jnp.int32))
            if rel:
                self.prefetch.release(name)
        return stream_stack

    def _moe_layer_granular_chunks(self, layer, xs, valid_lens, by_name,
                                   streaming):
        """Expert-granular MoE under layer-major prefill (DESIGN.md §9,
        §10): route EVERY chunk first, then demand-stream the union of the
        routed cold experts once — each cold expert crosses the link once
        per prompt instead of once per chunk. The pinned-expert phase of
        every chunk computes while those copies fly; the streamed stack is
        folded once and reused by every chunk's streamed phase (each
        expert row of the batched einsum depends only on its own weights,
        so the wider union stack never changes a chunk's bits)."""
        eng = self.engine
        E = self.cfg.moe.n_experts
        r_pl = by_name[f"L{layer}/moe.router"]
        w_r, rel_r = self._weights_for(r_pl, streaming)
        self.stats.engine_calls[r_pl.engine] += len(xs)
        routed = []
        demanded_union = set()
        for x, vl in zip(xs, valid_lens):
            disp, aux, idx = eng.moe_route_prefill_step(w_r, x, vl)
            idx_host = np.asarray(idx)
            # padded positions carry the out-of-range sentinel id E: they
            # must enter neither the demanded set nor the routing EMA
            idx_host = idx_host[idx_host < E]
            self._record_routing(layer, idx_host)
            demanded_union.update(int(e) for e in np.unique(idx_host))
            routed.append((disp, aux))
        if rel_r:
            self.prefetch.release(r_pl.sub.name)
        cold, streamed_cold = self._demand_cold_experts(
            layer, sorted(demanded_union), by_name)
        stack_pinned, mask = self._pinned_expert_stack(layer)
        bufs_p = [eng.moe_experts_step(stack_pinned, disp)
                  for disp, _ in routed]
        if cold:
            stream_stack = self._fold_cold_experts(layer, cold,
                                                   streamed_cold)
            bufs_s = [eng.moe_experts_step(stream_stack, disp)
                      for disp, _ in routed]
        else:
            bufs_s = bufs_p
        return [eng.moe_combine_step(x, bp, bs, mask, aux)
                for x, bp, bs, (_, aux) in zip(xs, bufs_p, bufs_s, routed)]

    # ------------------------------------------------------------ paged kv
    def _page_placement(self, cache, bid: int):
        """Synthetic demand-only placement for one paged-KV block restore
        (DESIGN.md §12). Never part of a plan (``kv_page`` is not a
        streamable kind) — fabricated per fault so restores ride the SAME
        demand pool, acquire/release protocol and streamed-bytes ledger as
        §9's cold experts, bucketed as "kv" in streamed_bytes_by_dtype."""
        sub = SubLayer(name=f"kvpage/{bid}", kind="kv_page", layer=0,
                       weight_bytes=cache.block_bytes,
                       meta={"quant": "kv", "bid": bid})
        return Placement(sub=sub, residency="sysram", engine="gpu",
                         streamed=True)

    def _page_fault_layer(self, cache, layer: int, page_stream: bool):
        """Restore this layer's faulted KV blocks before its attention
        step. Requests go out per layer, not per pass: a pass-wide sweep
        would queue later layers' pages ahead of an earlier MoE layer's
        expert demands in the FIFO demand queue and deadlock its bounded
        slots. Within the layer the restores still pipeline — every fault
        is enqueued before the first acquire, so block j+1 stages while
        block j folds (fold-then-release, like ``_fold_cold_experts``)."""
        faults = cache.begin_layer(layer)
        if not faults:
            return
        pls = [self._page_placement(cache, bid) for bid in faults]
        if page_stream:
            self.prefetch.request(pls)
            for pl, bid in zip(pls, faults):
                tree, rel = self._demand_acquire(pl)
                self._account_streamed(pl)
                cache.fold(bid, tree)
                if rel:
                    self.prefetch.release(pl.sub.name)
        else:
            # at-use restore: overlap disabled, or a straggler evicted
            # after this pass's demand sizing; _fetch_sync accounts the
            # streamed bytes
            for pl, bid in zip(pls, faults):
                cache.fold(bid, self._fetch_sync(pl))
        self.stats.page_faults += len(faults)
        self.stats.demanded_page_bytes += len(faults) * cache.block_bytes

    # ------------------------------------------------------------ passes
    def _begin_pass(self, tier: int, page_demand_bytes: int = 0):
        """Start one pass at ``tier``: begin the prefetch session over the
        tier plan's streamed placements and return ``(by_name, streaming)``
        for ``_weights_for`` lookups. Scratch sizing is read from the bound
        schedule's TierEntry each pass, so a live ``rebind`` re-sizes the
        next session's staging budget automatically (DESIGN.md §8).
        ``page_demand_bytes`` joins the demand-slot sizing when the pass
        expects paged-KV restores (DESIGN.md §12)."""
        entry = self.schedule.tiers[tier]
        plan = entry.plan
        self.stats.tiers_used.append(tier)
        by_name = {p.sub.name: p for p in plan.placements}
        # per-tier pin budgets can differ, so a sub-layer this executor
        # pinned (canonical min-tier set) may be marked streamed in the
        # picked tier's plan; it must not enter the prefetch queue or its
        # scratch slot would never be released. Expert shards never enter
        # the static queue either: they are demand-streamed — requested
        # mid-pass once each layer's router has selected them
        # (DESIGN.md §9).
        order, demand_bytes = [], 0
        self._demand_active = False
        # watchdog degradation (DESIGN.md §15): with a transfer worker
        # dead, later sessions run the overlap=False sync path — every
        # shard goes through _fetch_sync, which is bit-identical
        if self.prefetch is not None and not self.stats.degraded_sync:
            order = [p for p in plan.static_stream_order()
                     if p.sub.name not in self._pinned_names]
            demand_bytes = max(
                (p.sub.weight_bytes for p in plan.streamed_expert_placements()
                 if p.sub.name not in self._pinned_names), default=0)
            demand_bytes = max(demand_bytes, page_demand_bytes)
        streaming = {p.sub.name for p in order}
        started = bool(order) or demand_bytes > 0
        if started:
            self.prefetch.start(
                order, avail_bytes=max(entry.scratch_bytes - entry.act_bytes,
                                       0), demand_bytes=demand_bytes,
                pass_id=self._pass_id)
            self._demand_active = demand_bytes > 0
        return by_name, streaming, started

    @contextlib.contextmanager
    def _pass(self, kind: str, tier: int, page_demand_bytes: int = 0):
        """One pass over ``tier``'s plan inside an ``executor.pass`` span:
        begins it (``_begin_pass``), yields ``(by_name, streaming,
        started)``, and on the way out, raised or not, joins the prefetch
        session (``executor.pass_end``) and drops the pass's paged
        cache."""
        self._pass_id += 1
        with TraceAnnotation("executor.pass", kind=kind, tier=tier,
                             pass_id=self._pass_id):
            by_name, streaming, started = self._begin_pass(
                tier, page_demand_bytes=page_demand_bytes)
            try:
                yield by_name, streaming, started
            finally:
                if started:
                    with TraceAnnotation("executor.pass_end",
                                         pass_id=self._pass_id):
                        self.prefetch.finish()
                self._sync_stats()
                self._active_kvcache = None

    def _layer_loop(self, x, k, v, by_name, streaming, attn_fn, live=None):
        """Walk every layer's (attn, ffn/moe) sub-layers under the current
        pass's plan: fetch weights (pinned / prefetched / at-use), account
        engine calls and boundary hops, run the sub-layer, release scratch
        slots. ``attn_fn(w, x, k, v, i)`` supplies the attention step —
        chunked (`_attn_sub`) or fused decode (`attn_decode_step`).

        ``live`` is the caller's stacked KV dict. On an accelerator the
        attention steps donate their cache inputs, so when a pass fails
        part-way the dict is handed the newest (live) buffers before the
        exception propagates — a caller that survives the failure must
        never read the donated, deleted ones."""
        cfg = self.cfg
        prev_engine = None
        try:
            for i in range(cfg.n_layers):
                pa = by_name[f"L{i}/attn"]
                w, rel = self._weights_for(pa, streaming)
                self.stats.engine_calls[pa.engine] += 1
                if prev_engine is not None and prev_engine != pa.engine:
                    self.stats.boundary_hops += 1
                prev_engine = pa.engine
                x, k, v = attn_fn(w, x, k, v, i)
                if rel:
                    self.prefetch.release(pa.sub.name)
                if self.expert_granular:
                    pf = by_name[f"L{i}/moe.router"]
                    if prev_engine != pf.engine:
                        self.stats.boundary_hops += 1
                    prev_engine = pf.engine
                    x = self._moe_sub_granular(i, x, by_name, streaming)
                    continue
                pkey = f"L{i}/moe" if cfg.moe is not None else f"L{i}/ffn"
                pf = by_name[pkey]
                w, rel = self._weights_for(pf, streaming)
                self.stats.engine_calls[pf.engine] += 1
                if prev_engine != pf.engine:
                    self.stats.boundary_hops += 1
                prev_engine = pf.engine
                x = self._ffn_sub(w, x, streamed=pf.streamed)
                if rel:
                    self.prefetch.release(pf.sub.name)
        except BaseException:
            if live is not None:
                live["k"], live["v"] = k, v
            raise
        return x, k, v

    # ------------------------------------------------------------ forward
    def _run_chunk(self, tokens, kv, pos):
        """One pass over all sub-layers for a token chunk.

        kv: dict with stacked "k"/"v" arrays of shape (L, B, KV, S, hd).
        Only the final position's logits are computed — prefill and decode
        both consume just the last token, so the lm_head matmul over the
        earlier chunk positions would be dead FLOPs and (T x vocab) dead
        VRAM. Returns (B, 1, V) logits.
        """
        cfg = self.cfg
        self._check_alloc("chunk")
        tier = self.schedule.pick_tier(tokens.shape[0] * tokens.shape[1])
        with self._pass("chunk", tier) as (by_name, streaming, _):
            if self.engine is not None:
                x = self.engine.embed_step(self._embed_dev, tokens)
                k, v = kv["k"], kv["v"]
            else:
                x = jnp.take(self._embed_dev, tokens, axis=0)
                # per-layer list view; restacked once at the end of the pass
                k = [kv["k"][i] for i in range(cfg.n_layers)]
                v = [kv["v"][i] for i in range(cfg.n_layers)]
            pos_arr = jnp.asarray(pos, jnp.int32)
            x, k, v = self._layer_loop(
                x, k, v, by_name, streaming,
                lambda w, x, k, v, i: self._attn_sub(w, x, k, v, i, pos_arr,
                                                     pos),
                live=kv if self.engine is not None else None)
            # slice the final position BEFORE the head: the (B, 1, d) shape
            # also matches the decode head call, so prefill shares its
            # executable instead of compiling a (B, T, d) variant per tier
            if self.engine is not None:
                logits = self.engine.head_step(self._final_dev,
                                               self._unembed_dev, x[:, -1:])
            else:
                xl = rmsnorm(x[:, -1:], self._final_dev, cfg.norm_eps)
                logits = xl @ self._unembed_dev
        if self.engine is None:
            k, v = jnp.stack(k), jnp.stack(v)
        return logits, {"k": k, "v": v}

    def _run_decode(self, tokens, kv, pos_vec, active, n_active: int):
        """One fused multi-slot decode iteration (DESIGN.md §7).

        tokens: (B, 1) last token per slot; pos_vec: (B,) per-slot cache
        positions; active: (B,) bool slot mask; n_active: batch-wide new
        token count (drives the tier pick, paper PickTier). All slots run
        through one batched pass, so every streamed sub-layer crosses the
        link exactly once per iteration — the per-slot baseline pays the
        copy cost once per active slot instead.
        """
        assert self.engine is not None, "fused decode requires the jitted " \
            "engine (jit_engine=True)"
        # alloc check BEFORE prepare_decode touches the page table: an
        # abort here leaves no state to unwind, so the serving ladder can
        # simply re-run the iteration after degrading (DESIGN.md §15)
        self._check_alloc("decode")
        paged = isinstance(kv, PagedKVCache)
        page_demand = 0
        if paged:
            # host-side page-table work: allocate this iteration's write
            # blocks, find the faulted (host-evicted) ones (DESIGN.md §12)
            pos_h = np.asarray(pos_vec)
            act_h = np.asarray(active)
            faults = kv.prepare_decode({int(s): int(pos_h[s])
                                        for s in range(len(act_h))
                                        if act_h[s]})
            page_demand = kv.block_bytes if faults else 0
            self._active_kvcache = kv
        tier = self.schedule.pick_decode_tier(
            n_active, queue_depth=self.sched_queue_depth,
            slack_s=self.sched_slack_s)
        streamed_before = self.stats.streamed_bytes
        demanded_before = (self.stats.expert_demanded,
                           self.stats.expert_hits,
                           self.stats.demanded_expert_bytes)
        with self._pass("decode", tier, page_demand) as (by_name, streaming,
                                                         started):
            page_stream = paged and started and self._demand_active
            x = self.engine.embed_step(self._embed_dev, tokens)
            if paged:
                def paged_attn(w, x, k, v, i):
                    self._page_fault_layer(kv, i, page_stream)
                    x, kv.k_pool, kv.v_pool = \
                        self.engine.attn_decode_paged_step(
                            w, x, kv.k_pool, kv.v_pool, kv.layer_table(i),
                            pos_vec, active)
                    kv.end_layer(i)
                    return x, k, v

                x, _, _ = self._layer_loop(x, None, None, by_name,
                                           streaming, paged_attn)
            else:
                k, v = kv["k"], kv["v"]
                x, k, v = self._layer_loop(
                    x, k, v, by_name, streaming,
                    lambda w, x, k, v, i: self.engine.attn_decode_step(
                        w, x, k, v, self._layer_ids[i], pos_vec, active),
                    live=kv)
            logits = self.engine.head_step(self._final_dev,
                                           self._unembed_dev, x)
        self.stats.decode_passes += 1
        self.stats.pass_streamed_bytes.append(
            self.stats.streamed_bytes - streamed_before)
        if self.expert_granular:
            d0, h0, b0 = demanded_before
            demanded = self.stats.expert_demanded - d0
            self.stats.pass_expert_stats.append({
                "demanded": demanded,
                "hits": self.stats.expert_hits - h0,
                "demanded_bytes": self.stats.demanded_expert_bytes - b0,
                "resident_bytes": self.stats.resident_expert_bytes,
                "hit_rate": (self.stats.expert_hits - h0)
                / max(demanded, 1),
            })
        return logits, (kv if paged else {"k": k, "v": v})

    def _run_verify(self, tokens, kv, pos_vec, active, n_active: int):
        """One speculative verify pass (DESIGN.md §14): score ``W = k+1``
        positions per active slot in a single streamed pass.

        tokens: (B, W) — column 0 is each slot's last committed token at
        ``pos_vec``; columns 1..k are the draft's proposals. Embedding,
        FFN/MoE and the head run fused over the whole (B, W) window, but
        attention advances as a *wavefront*: W sequential calls of the
        SAME jitted decode executables serving uses, one per window
        column. That makes the pass bit-identical to W sequential decode
        steps by construction — the fused ops are bitwise row-equal
        across widths (elementwise / row-independent matmuls), and each
        attention call sees exactly the cache state sequential decode
        would. (A fused multi-position attention step is NOT safe: XLA
        fuses the decode einsum with the cache-update select differently
        per shape, drifting bf16 by one ulp.) The weights still cross
        the link once per layer per pass — one crossing of the streamed
        plan for up to W accepted tokens instead of one per token — and
        a cold MoE expert is demanded once per layer per window instead
        of once per token. Rejected KV suffixes are undone by
        ``rollback_kv``.

        The tier pick sees ``n_active * W`` new tokens: a verify pass IS
        a batch-wide token count of that size in the paper's PickTier
        sense, so wider speculation legitimately steps the tier up.

        Returns ``(logits, kv)`` with logits of shape (B, W, V).
        """
        assert self.engine is not None, "speculative verify requires the " \
            "jitted engine (jit_engine=True)"
        self._check_alloc("verify")
        B, W = tokens.shape
        paged = isinstance(kv, PagedKVCache)
        page_demand = 0
        if paged:
            pos_h = np.asarray(pos_vec)
            act_h = np.asarray(active)
            faults = kv.prepare_verify({int(s): int(pos_h[s])
                                        for s in range(len(act_h))
                                        if act_h[s]}, W)
            page_demand = kv.block_bytes if faults else 0
            self._active_kvcache = kv
        tier = self.schedule.pick_decode_tier(
            n_active * W, queue_depth=self.sched_queue_depth,
            slack_s=self.sched_slack_s)
        streamed_before = self.stats.streamed_bytes
        expert_bytes_before = self.stats.demanded_expert_bytes
        page_bytes_before = self.stats.demanded_page_bytes
        # per-pass static plan bytes for the hard ledger (DESIGN.md §14):
        # what this tier's plan streams regardless of demand traffic
        static_bytes = sum(
            p.sub.weight_bytes
            for p in self.schedule.tiers[tier].plan.static_stream_order()
            if p.sub.name not in self._pinned_names)
        with self._pass("verify", tier, page_demand) as (by_name, streaming,
                                                         started):
            page_stream = paged and started and self._demand_active
            x = self.engine.embed_step(self._embed_dev, tokens)
            if paged:
                def paged_attn(w, x, k, v, i):
                    self._page_fault_layer(kv, i, page_stream)
                    # table is static across the window: prepare_verify
                    # mapped all W positions up front, the wavefront only
                    # mutates the pools
                    table = kv.layer_table(i)
                    cols = []
                    for j in range(W):
                        xj, kv.k_pool, kv.v_pool = \
                            self.engine.attn_decode_paged_step(
                                w, x[:, j:j + 1], kv.k_pool, kv.v_pool,
                                table, pos_vec + j, active)
                        cols.append(xj)
                    kv.end_layer(i)
                    return jnp.concatenate(cols, axis=1), k, v

                x, _, _ = self._layer_loop(x, None, None, by_name,
                                           streaming, paged_attn)
            else:
                def stacked_attn(w, x, k, v, i):
                    cols = []
                    for j in range(W):
                        xj, k, v = self.engine.attn_decode_step(
                            w, x[:, j:j + 1], k, v, self._layer_ids[i],
                            pos_vec + j, active)
                        cols.append(xj)
                    return jnp.concatenate(cols, axis=1), k, v

                k, v = kv["k"], kv["v"]
                x, k, v = self._layer_loop(x, k, v, by_name, streaming,
                                           stacked_attn, live=kv)
            # unlike _run_chunk the head scores ALL W positions — the
            # acceptance loop needs the target's argmax at every one
            logits = self.engine.head_step(self._final_dev,
                                           self._unembed_dev, x)
        self.stats.spec_verify_passes += 1
        self.stats.verify_pass_stats.append({
            "width": W,
            "streamed_bytes": self.stats.streamed_bytes - streamed_before,
            "static_plan_bytes": static_bytes,
            "demanded_expert_bytes":
                self.stats.demanded_expert_bytes - expert_bytes_before,
            "demanded_page_bytes":
                self.stats.demanded_page_bytes - page_bytes_before,
        })
        return logits, (kv if paged else {"k": k, "v": v})

    def rollback_kv(self, kv, keep_pos, rollback_mask):
        """Undo the KV writes a verify pass made for rejected positions
        (DESIGN.md §14). ``keep_pos[b]`` is the first cache index to clear
        for slot ``b`` (== old pos + accepted count); ``rollback_mask[b]``
        selects the slots that actually rejected a suffix. Stacked caches
        zero the tail in one jitted masked write — byte-identical to never
        having written on a fresh (zero-initialised) cache; paged caches
        truncate through the page table, releasing whole rejected blocks
        and zeroing the partial one (COW-safe: the verify pass wrote into
        this slot's private blocks)."""
        if isinstance(kv, PagedKVCache):
            keep_h = np.asarray(keep_pos)
            mask_h = np.asarray(rollback_mask)
            for s in range(len(mask_h)):
                if mask_h[s]:
                    kv.truncate(int(s), int(keep_h[s]))
            return kv
        k, v = self.engine.rollback_step(
            kv["k"], kv["v"], jnp.asarray(keep_pos, jnp.int32),
            jnp.asarray(rollback_mask))
        return {"k": k, "v": v}

    def init_kv(self, batch):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        if self.kv_layout == "paged":
            n_pages = None if self.kv_pool_pages is None \
                else self.kv_pool_pages + 1      # + the null write sink
            cache = PagedKVCache(cfg, batch, self.max_seq,
                                 page_size=self.kv_page_size,
                                 n_pages=n_pages)
            cache.fault_plan = self.faults    # alloc.host injection (§15)
            cache.fold_step = self.engine.fold_page_step
            # warm the fold executable now (against the null sink): the
            # first real fault lands mid-serve and must not pay a compile —
            # the same no-retrace rationale as fold_expert_step (§8)
            zp = jnp.zeros((cfg.n_kv_heads, self.kv_page_size, hd),
                           jnp.bfloat16)
            cache.k_pool, cache.v_pool = cache.fold_step(
                cache.k_pool, cache.v_pool, zp, zp,
                jnp.asarray(NULL_PAGE, jnp.int32))
            return cache
        shape = (cfg.n_layers, batch, cfg.n_kv_heads, self.max_seq, hd)
        return {"k": jnp.zeros(shape, jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.bfloat16)}

    def prefill(self, tokens, kv=None, prefill_mode: str | None = None,
                slot: int | None = None):
        """Chunked prefill at the planner-picked tier size (DESIGN.md §10).

        ``prefill_mode`` overrides the executor default for this call:
        ``"layer_major"`` streams each sub-layer once per prompt and runs
        every chunk against the resident weights (weight-stationary);
        ``"chunk_major"`` is the chunk-major baseline, one full plan pass
        per chunk. ``kv`` lets a caller (the serving batcher) prefill into
        an existing cache view instead of a fresh one; ``slot`` targets one
        row of that shared cache (B must be 1) through the engine's donated
        slot-threaded step instead of a serving-side whole-slot slice
        write (DESIGN.md §12). A paged ``kv`` also runs the prefix-cache
        lookup here: matched full blocks are mapped read-only and only the
        suffix is computed.
        """
        mode = prefill_mode if prefill_mode is not None else \
            self.prefill_mode
        if mode not in ("layer_major", "chunk_major"):
            # same contract as the constructor: a typo'd override must not
            # silently fall through to the chunk-major branch (and label
            # its prefill_stats entry with the bogus mode)
            raise ValueError(f"unknown prefill_mode {mode!r}")
        if mode == "layer_major" and self.engine is None:
            raise ValueError("prefill_mode='layer_major' requires the "
                             "jitted engine (jit_engine=True)")
        B, T = tokens.shape
        # alloc check at the very top — before prefix_attach/prepare_*
        # touch the page table, so an abort is clean to retry (§15)
        self._check_alloc("prefill")
        if kv is None:
            kv = self.init_kv(B)
        paged = isinstance(kv, PagedKVCache)
        if (paged or slot is not None) and mode != "layer_major":
            raise ValueError("paged / slot-targeted prefill runs "
                             "layer-major only (jitted engine)")
        if slot is not None and B != 1:
            raise ValueError("slot-targeted prefill admits ONE sequence")
        rows = None
        pos0 = 0
        page_demand = 0
        if paged:
            rows = [slot] if slot is not None else list(range(B))
            tok_np = np.asarray(tokens)
            if B == 1:
                # prefix-cache lookup (DESIGN.md §12): map shared full
                # blocks read-only, prefill only the suffix
                pos0 = kv.prefix_attach(rows[0], tok_np[0])
            faults = kv.prepare_prefill([(r, T, pos0) for r in rows])
            page_demand = kv.block_bytes if faults else 0
            self._active_kvcache = kv
        if mode == "layer_major":
            tier = self.schedule.pick_prefill_tier(
                B * (T - pos0), min_tier=B,
                queue_depth=self.sched_queue_depth)
        else:
            tier = self.schedule.pick_tier(B * T)
        if tier // B < 1:
            raise ValueError(
                f"picked tier {tier} cannot chunk a batch of {B} sequences "
                "(tier // batch < 1 token per sequence per chunk); widen "
                "the tier table or shrink the batch")
        before = self._prefill_snapshot()
        if mode == "layer_major":
            # always the full tier chunk — a short prompt pads up instead
            # of shrinking the chunk, so ONE executable serves every
            # prompt length at this tier (no re-trace across chunk counts
            # or tails)
            chunk = tier // B
            try:
                logits, kv, ring_bytes = self._prefill_layer_major(
                    tokens if pos0 == 0 else tokens[:, pos0:], kv, chunk,
                    tier, slot=slot, rows=rows, pos0=pos0,
                    page_demand=page_demand)
            finally:
                self._active_kvcache = None
            if paged and B == 1:
                kv.prefix_register(rows[0], tok_np[0])
            chunks = -(-(T - pos0) // chunk)
        else:
            chunk = min(T, tier // B)
            logits = None
            pos = 0
            chunks = 0
            # chunk-major holds ONE chunk's residual at a time — the
            # memory side of the memory-for-bandwidth trade (DESIGN.md §10)
            ring_bytes = B * chunk * self.cfg.d_model * 2
            while pos < T:
                end = min(T, pos + chunk)
                logits, kv = self._run_chunk(tokens[:, pos:end], kv, pos)
                self.stats.prefill_passes += 1
                chunks += 1
                pos = end
        self._record_prefill(mode, chunks, before, ring_bytes,
                             tokens=T - pos0, prefix_tokens=pos0)
        return logits[:, -1:], kv, T

    def _prefill_layer_major(self, tokens, kv, chunk: int, tier: int,
                             slot: int | None = None, rows=None,
                             pos0: int = 0, page_demand: int = 0):
        """Weight-stationary prefill (DESIGN.md §10): ONE prefetch session
        per prompt; for each sub-layer in stream order, all chunks run
        against the resident weights before the stream advances — so each
        streamed/demanded shard crosses the link once per prompt instead
        of once per chunk. Causally valid: chunk c's attention at layer L
        reads only the layer-L KV prefix, which chunks 0..c-1 wrote
        earlier in this same layer step. Per-chunk activations live in a
        ring of C ``(B, chunk, d)`` buffers (total == one full-prompt
        residual); the stacked KV cache is written in place as always. The
        tail chunk is padded to ``chunk`` (one executable regardless of
        chunk count or tail size) and masked out of the KV cache and the
        MoE routing capacity by the engine's ``*_prefill_step`` variants.
        """
        cfg = self.cfg
        eng = self.engine
        paged = isinstance(kv, PagedKVCache)
        B, T = tokens.shape          # T: SUFFIX length (tokens after pos0)
        C = -(-T // chunk)
        tail = T - (C - 1) * chunk
        # pad the tail chunk to the chunk size so one executable serves any
        # chunk count/tail — UNLESS (a) the padded cache-write window would
        # run past max_seq (dynamic_update_slice clamps the start there,
        # which would shift the write over valid positions) or (b) an MoE
        # chunk would leave the dropless capacity regime (padding grows
        # capacity_of's token count, and a truncating capacity could keep
        # assignments the unpadded baseline drops). Either way the tail
        # runs at its natural shape instead — one extra trace, bit-exact
        # always.
        pad_ok = pos0 + C * chunk <= self.max_seq and (
            cfg.moe is None
            or mlp_mod.capacity_is_dropless(B * chunk, cfg.moe))
        pad = C * chunk - T if pad_ok else 0
        if pad:
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        slot_arr = None if slot is None else jnp.asarray(slot, jnp.int32)
        with self._pass("prefill", tier, page_demand) as (by_name, streaming,
                                                          started):
            page_stream = paged and started and self._demand_active
            try:
                k = v = None
                if not paged:
                    k, v = kv["k"], kv["v"]
                xs = [eng.embed_step(
                          self._embed_dev,
                          tokens[:, c * chunk:min((c + 1) * chunk,
                                                  tokens.shape[1])])
                      for c in range(C)]
                pos_c = [jnp.asarray(pos0 + c * chunk, jnp.int32)
                         for c in range(C)]
                valid_c = [jnp.asarray(chunk if c < C - 1 else tail, jnp.int32)
                           for c in range(C)]
                prev_engine = None
                for i in range(cfg.n_layers):
                    pa = by_name[f"L{i}/attn"]
                    w, rel = self._weights_for(pa, streaming)
                    self.stats.engine_calls[pa.engine] += C
                    if prev_engine is not None and prev_engine != pa.engine:
                        self.stats.boundary_hops += 1
                    prev_engine = pa.engine
                    if paged:
                        # restore this layer's faulted blocks, then run every
                        # chunk against the layer's physical page table
                        self._page_fault_layer(kv, i, page_stream)
                        table = kv.layer_table(i, rows=rows)
                        for c in range(C):
                            xs[c], kv.k_pool, kv.v_pool = \
                                eng.attn_prefill_paged_step(
                                    w, xs[c], kv.k_pool, kv.v_pool, table,
                                    pos_c[c], valid_c[c])
                        kv.end_layer(i)
                    elif slot is not None:
                        for c in range(C):
                            xs[c], k, v = eng.attn_prefill_slot_step(
                                w, xs[c], k, v, self._layer_ids[i], slot_arr,
                                pos_c[c], valid_c[c])
                    else:
                        for c in range(C):
                            xs[c], k, v = eng.attn_prefill_step(
                                w, xs[c], k, v, self._layer_ids[i], pos_c[c],
                                valid_c[c])
                    if rel:
                        self.prefetch.release(pa.sub.name)
                    if self.expert_granular:
                        pf = by_name[f"L{i}/moe.router"]
                        if prev_engine != pf.engine:
                            self.stats.boundary_hops += 1
                        prev_engine = pf.engine
                        xs = self._moe_layer_granular_chunks(
                            i, xs, valid_c, by_name, streaming)
                        continue
                    pkey = f"L{i}/moe" if cfg.moe is not None else f"L{i}/ffn"
                    pf = by_name[pkey]
                    w, rel = self._weights_for(pf, streaming)
                    self.stats.engine_calls[pf.engine] += C
                    if prev_engine != pf.engine:
                        self.stats.boundary_hops += 1
                    prev_engine = pf.engine
                    for c in range(C):
                        if cfg.moe is not None:
                            xs[c] = eng.moe_prefill_step(w, xs[c], valid_c[c])
                        else:
                            xs[c] = eng.ffn_step(w, xs[c],
                                                 streamed=pf.streamed)
                    if rel:
                        self.prefetch.release(pf.sub.name)
                # final logits from the last VALID position only (the padded
                # rows are garbage); (B, 1, d) shares the decode head
                # executable
                x_last = xs[-1][:, tail - 1:tail]
                logits = eng.head_step(self._final_dev, self._unembed_dev,
                                       x_last)
            except BaseException:
                # hand the live stacked caches back, as _layer_loop does: the
                # attention steps donated the caller's buffers on accelerators
                if not paged and k is not None:
                    kv["k"], kv["v"] = k, v
                raise
        self.stats.prefill_passes += 1
        # the realised activation ring: every chunk's residual held at
        # once, ~one full-prompt residual (DESIGN.md §10 accounting)
        ring_bytes = B * tokens.shape[1] * cfg.d_model * 2
        return logits, (kv if paged else {"k": k, "v": v}), ring_bytes

    def _prefill_snapshot(self):
        s = self.stats
        return (s.streamed_bytes, s.demanded_expert_bytes, s.copy_s_hidden,
                s.copy_s_exposed, s.prefill_passes, s.demanded_page_bytes)

    def _record_prefill(self, mode, chunks, before, ring_bytes,
                        tokens=0, prefix_tokens=0):
        s = self.stats
        s.prefill_stats.append({
            "mode": mode,
            "chunks": chunks,
            # prefilled suffix vs prefix-cache coverage (DESIGN.md §12):
            # a prefix hit shows up as prefix_tokens > 0 and a shorter
            # tokens count, NOT as fewer chunks (the tier re-picks)
            "tokens": tokens,
            "prefix_tokens": prefix_tokens,
            "act_ring_bytes": ring_bytes,
            "passes": s.prefill_passes - before[4],
            "streamed_bytes": s.streamed_bytes - before[0],
            "demanded_expert_bytes": s.demanded_expert_bytes - before[1],
            "copy_s_hidden": s.copy_s_hidden - before[2],
            "copy_s_exposed": s.copy_s_exposed - before[3],
            "demanded_page_bytes": s.demanded_page_bytes - before[5],
        })

    def decode(self, last_tokens, kv, pos, steps=8, greedy=True):
        """Greedy decode loop; returns generated tokens."""
        out = []
        tok = last_tokens
        if isinstance(kv, PagedKVCache):
            # paged decode runs the fused multi-slot pass with every row
            # active (the serving batcher calls _run_decode directly)
            B = tok.shape[0]
            active = jnp.ones((B,), bool)
            for s in range(steps):
                pos_vec = jnp.full((B,), pos + s, jnp.int32)
                logits, kv = self._run_decode(tok, kv, pos_vec, active, B)
                tok = greedy_token(logits[:, -1:])
                out.append(np.asarray(tok)[:, 0])
            return np.stack(out, axis=1), kv
        for s in range(steps):
            logits, kv = self._run_chunk(tok, kv, pos + s)
            tok = greedy_token(logits[:, -1:])
            out.append(np.asarray(tok)[:, 0])
        return np.stack(out, axis=1), kv
