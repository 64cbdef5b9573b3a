"""`repro.Session` — one front door for plan -> install -> serve, with live
re-planning under changing VRAM budgets (DESIGN.md §8).

The paper's headline is not just fast offloaded inference but inference that
"flexibly adapts to system and inference conditions": the IGI-SDK scenario
where a game claims or releases VRAM mid-session and the scheduler must
re-plan without dropping in-flight requests. A Session owns that lifecycle:

    s = Session.open(cfg, system=CLI2, budget_bytes=2 << 30)
    tokens = s.generate(prompts, max_new_tokens=16)   # prefill + decode
    s.serve(requests)                                 # continuous batching
    diff = s.update_budget(1 << 30)                   # live re-plan: moves
    s.serve(more)                                     #   only diff bytes

``open`` runs (or reuses) the install-phase profile DB, shards the model
into sub-layers, and plans the tier table; the executor, model parameters
and the continuous batcher are built lazily on first use, so planning-only
sessions (full-size configs) never allocate weights.

``update_budget`` / ``update_setting`` re-run the planner under the new
conditions, diff the old vs new pinned sets (``Schedule.diff``) and apply
the delta incrementally (``PipelinedExecutor.rebind``): only changed
sub-layer weights are pinned/evicted, the stacked KV caches and the jitted
engine executables survive, so in-flight decode slots keep generating the
exact same tokens across the swap.
"""
from __future__ import annotations

from dataclasses import replace
from typing import List, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (SYSTEMS, InferenceSetting, PipelinedExecutor,
                        Schedule, ScheduleDiff, SpecDecoder, SystemConfig,
                        TimingEstimator, build_graph, build_schedule,
                        choose_spec_k, estimate_spec_tps, estimate_tps,
                        estimate_ttft, plan_draft_carve, run_install)
from repro.core.costmodel import kv_block_bytes
from repro.core.faults import (DEGRADATION_RUNGS, FaultPlan,
                               RecoveryPolicy)
from repro.core.kvpaged import PAGE_SIZE
from repro.core.planner import TIERS
from repro.core.serving import ContinuousBatcher, Request
from repro.models import build_model
from repro.models.common import greedy_token


class Session:
    """Owns profile DB + schedule + executor + batcher for one model on one
    system, and re-plans live when the conditions change (DESIGN.md §8)."""

    def __init__(self, cfg, system: SystemConfig, budget_bytes: int,
                 setting: InferenceSetting, *, db=None, params=None,
                 wdtype: float = 2.0, max_seq: int = 256, tiers=TIERS,
                 overlap: bool = True, jit_engine: bool = True,
                 quick_install: bool = True,
                 expert_granular: Optional[bool] = None,
                 prefill_mode: Optional[str] = None,
                 kv_layout: Optional[str] = None,
                 kv_page_size: Optional[int] = None,
                 kv_pool_pages: Optional[int] = None,
                 draft_cfg=None, draft_params=None, spec_k: int = 0,
                 sampling: str = "greedy",
                 faults: Optional[FaultPlan] = None,
                 recovery: Optional[RecoveryPolicy] = None):
        self.cfg = cfg
        self.system = system
        self.setting = setting
        self.budget_bytes = budget_bytes
        self.max_seq = max_seq
        self.tiers = tiers
        self.overlap = overlap
        self.jit_engine = jit_engine
        # layer-major weight-stationary prefill is the default on the
        # jitted engine (DESIGN.md §10); "chunk_major" keeps the baseline.
        # An explicit "layer_major" that cannot be honoured raises here —
        # not lazily at first executor use (same contract as
        # expert_granular below).
        if prefill_mode not in (None, "layer_major", "chunk_major"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if prefill_mode == "layer_major" and not jit_engine:
            raise ValueError("prefill_mode='layer_major' requires the "
                             "jitted engine (jit_engine=True)")
        self.prefill_mode = prefill_mode
        # paged KV cache (DESIGN.md §12): "paged" swaps the stacked
        # (L,B,KV,S,hd) cache for the page-pool layout with LRU eviction and
        # prefix reuse. Same raise-early contract as the knobs above; an
        # unhonourable explicit choice fails at open(), not at first use.
        if kv_layout not in (None, "stacked", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_layout == "paged" and not jit_engine:
            raise ValueError("kv_layout='paged' requires the jitted engine "
                             "(jit_engine=True)")
        self.kv_layout = kv_layout or "stacked"
        self.kv_page_size = int(kv_page_size) if kv_page_size else None
        self.kv_pool_pages = kv_pool_pages
        # speculative decoding (DESIGN.md §14): raise-early contracts,
        # same pattern as the knobs above — a combination that would
        # silently produce divergent tokens fails at open(), not at the
        # first serve iteration
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k > 0 and sampling != "greedy":
            raise ValueError(
                f"spec_k={spec_k} requires greedy sampling (got "
                f"sampling={sampling!r}): longest-prefix acceptance is "
                "defined against the target's argmax — speculation under "
                "a non-greedy knob would silently produce divergent "
                "tokens")
        if sampling != "greedy":
            raise ValueError(f"sampling={sampling!r} is not supported "
                             "(only 'greedy')")
        if spec_k > 0 and draft_cfg is None:
            raise ValueError("spec_k > 0 needs a draft model "
                             "(Session.open(draft_cfg=...))")
        if draft_cfg is not None:
            if not jit_engine:
                raise ValueError("speculative decoding requires the jitted "
                                 "engine (jit_engine=True)")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft/target vocab mismatch: draft {draft_cfg.name} "
                    f"has vocab={draft_cfg.vocab}, target {cfg.name} has "
                    f"vocab={cfg.vocab} — the draft's token ids would not "
                    "mean the same strings, so acceptance would compare "
                    "apples to oranges")
            if draft_cfg.tokenizer is not None and cfg.tokenizer is not None \
                    and draft_cfg.tokenizer != cfg.tokenizer:
                raise ValueError(
                    f"draft/target tokenizer mismatch: draft uses "
                    f"{draft_cfg.tokenizer!r}, target uses "
                    f"{cfg.tokenizer!r} — equal vocab sizes do not make "
                    "the id spaces compatible across tokenizers")
        self.sampling = sampling
        self.draft_cfg = draft_cfg
        self.spec_k = int(spec_k)
        self._draft_params = draft_params
        self.db = db if db is not None else run_install(system,
                                                        quick=quick_install)
        self.est = TimingEstimator(self.db, system)
        # MoE models default to expert-granular placement (DESIGN.md §9):
        # the planner pins hot experts individually (routing stats seeded
        # from the profile DB, refined online via the executor's EMA) and
        # the runtime demand-streams only router-selected cold experts.
        # An explicit True that cannot be honoured raises instead of being
        # silently coerced (same contract as batcher(max_batch/fused)).
        if expert_granular is None:
            expert_granular = cfg.moe is not None and jit_engine
        elif expert_granular:
            if cfg.moe is None:
                raise ValueError(
                    "expert_granular=True requires an MoE config "
                    f"({cfg.name} has no moe block)")
            if not jit_engine:
                raise ValueError("expert_granular=True requires the jitted "
                                 "engine (jit_engine=True)")
        self.expert_granular = bool(expert_granular)
        routing = self.db.get_routing(cfg.name) if self.expert_granular \
            else None
        self.subs = build_graph(cfg, wdtype=wdtype,
                                expert_granular=self.expert_granular,
                                routing=routing)
        # draft-plan budget split (DESIGN.md §14): with speculation
        # requested, the planner first carves the draft's wholly-pinned
        # residency out of the budget and the target plans over the
        # remainder; infeasible (or spec_k=0) leaves the target's plan at
        # the FULL budget — byte-for-byte what a spec-free session builds
        self.draft_subs = build_graph(draft_cfg, wdtype=wdtype) \
            if draft_cfg is not None else None
        self.draft_schedule: Optional[Schedule] = None
        self.draft_carve_bytes = 0
        if self.spec_k > 0:
            self.draft_schedule, self.draft_carve_bytes = plan_draft_carve(
                budget_bytes, self.draft_subs, self.subs, self.est,
                setting, tiers)
        self.schedule: Schedule = build_schedule(
            budget_bytes - self.draft_carve_bytes, self.subs, self.est,
            setting, tiers, kv_page_size=self.kv_page_size or PAGE_SIZE)
        self.replan_log: List[ScheduleDiff] = []
        # fault injection + graceful degradation (DESIGN.md §15): the
        # FaultPlan threads through the executor into the prefetch/demand
        # pools and the paged cache; the ladder state below tracks how far
        # an emergency rebudget has walked this session down
        self.faults = faults
        self.recovery = recovery
        self.degradation_level = 0
        self.degrade_log: List[dict] = []
        self._emergency_reserve_bytes = 0
        self._params = params
        self._executor: Optional[PipelinedExecutor] = None
        self._batcher: Optional[ContinuousBatcher] = None
        self._batcher_cfg = None   # (max_batch, fused) as requested
        self._spec_decoder: Optional[SpecDecoder] = None

    # ------------------------------------------------------------ open
    @classmethod
    def open(cls, cfg, system: Union[SystemConfig, str] = "cli2",
             budget_bytes: int = 4 << 30,
             setting: Optional[InferenceSetting] = None, **kw) -> "Session":
        """Install (or reuse a profile DB via ``db=``), plan the tier table,
        and return a Session ready to generate/serve. ``system`` accepts a
        ``SystemConfig`` or a name from ``repro.core.SYSTEMS``."""
        if isinstance(system, str):
            system = SYSTEMS[system]
        return cls(cfg, system, budget_bytes,
                   setting or InferenceSetting(), **kw)

    # ------------------------------------------------------------ lazy build
    @staticmethod
    def _init_on_host(cfg, seed: int):
        """Random parameters built on the host CPU device ("sysRAM"): the
        executor copies them into its per-sub-layer host tree and puts on
        the accelerator only what the plan pins or streams, so the device
        never holds the whole model beside the pinned set."""
        with jax.default_device(jax.devices("cpu")[0]):
            return build_model(cfg).init(jax.random.PRNGKey(seed))

    @property
    def params(self):
        if self._params is None:
            self._params = self._init_on_host(self.cfg, 0)
        return self._params

    @property
    def draft_params(self):
        if self._draft_params is None and self.draft_cfg is not None:
            # a different seed than the target's on purpose: a randomly
            # initialised draft disagrees with the target almost always,
            # exercising the rollback path; callers wanting a high accept
            # rate pass the target's params (self-speculation) or real
            # draft weights explicitly
            self._draft_params = self._init_on_host(self.draft_cfg, 1)
        return self._draft_params

    @property
    def spec_active(self) -> bool:
        """True when speculation is live: requested (spec_k > 0) AND the
        current budget fits the draft wholly in VRAM (DESIGN.md §14)."""
        return self.spec_k > 0 and self.draft_schedule is not None

    def spec_decoder(self, max_batch: int) -> Optional[SpecDecoder]:
        """The session's draft runner (built on first call when
        speculation is live; ``None`` otherwise). The decoder survives a
        mid-serve feasibility flip — only the batcher's ``spec_k``
        gates whether iterations consult it."""
        if not self.spec_active:
            return self._spec_decoder
        if self._spec_decoder is None:
            self._spec_decoder = SpecDecoder(
                self.draft_cfg, self.draft_params, self.draft_schedule,
                max_batch=max_batch, max_seq=self.max_seq)
        return self._spec_decoder

    @property
    def executor(self) -> PipelinedExecutor:
        """The bound executor (built on first use; planning-only sessions
        never construct it)."""
        if self._executor is None:
            assert self.cfg.family in ("dense", "moe"), \
                "execution covers the dense/moe families; this session is " \
                "planning-only"
            self._executor = PipelinedExecutor(
                self.cfg, self.params, self.schedule, max_seq=self.max_seq,
                overlap=self.overlap, jit_engine=self.jit_engine,
                prefill_mode=self.prefill_mode, kv_layout=self.kv_layout,
                kv_page_size=self.kv_page_size,
                kv_pool_pages=self._effective_kv_pool_pages(),
                faults=self.faults, recovery=self.recovery)
        return self._executor

    def _effective_kv_pool_pages(self) -> Optional[int]:
        """Page-pool size the executor gets: an explicit ``kv_pool_pages``
        wins; otherwise the planner's ``Schedule.kv_pool_bytes`` converted
        to pages (DESIGN.md §12). ``None`` (stacked layout, or a graph with
        no kv subs) leaves the executor's ample never-evicting default."""
        if self.kv_pool_pages is not None or self.kv_layout != "paged":
            return self.kv_pool_pages
        if self.schedule.kv_pool_bytes <= 0:
            return None
        kv_subs = [s for s in self.subs if s.kind == "kv"]
        if not kv_subs:
            return None
        block = max(kv_block_bytes(s, self.schedule.kv_page_size)
                    for s in kv_subs)
        return max(1, self.schedule.kv_pool_bytes // block)

    def batcher(self, max_batch: Optional[int] = None,
                fused: Optional[bool] = None) -> ContinuousBatcher:
        """The session's continuous batcher. Created on first call (with
        ``max_batch=4, fused=True`` defaults); later calls return the same
        live batcher, slots and all — ``None`` means "keep as built", and a
        conflicting explicit value raises instead of being silently
        ignored (the KV layout is fixed at the executor)."""
        if self._batcher is None:
            mb = 4 if max_batch is None else max_batch
            fu = True if fused is None else fused
            self._batcher = ContinuousBatcher.from_session(
                self, max_batch=mb, fused=fu)
            # remember the REQUESTED values: the batcher's own .fused is
            # the effective one (anded with jit_engine), and comparing
            # against that would reject a repeat of the original argument
            self._batcher_cfg = (mb, fu)
            return self._batcher
        mb_built, fu_built = self._batcher_cfg
        if max_batch is not None and max_batch != mb_built:
            raise ValueError(
                f"session batcher was built with max_batch={mb_built}; "
                f"cannot serve with {max_batch} (close() the session to "
                "rebuild)")
        if fused is not None and fused != fu_built:
            raise ValueError(
                f"session batcher was built with fused={fu_built}; cannot "
                f"serve with fused={fused} (close() the session to "
                "rebuild)")
        return self._batcher

    # ------------------------------------------------------------ inference
    def generate(self, prompts, max_new_tokens: int = 8) -> np.ndarray:
        """Greedy batch generation: chunked prefill at the planner-picked
        tier, then decode. prompts: (B, T) int tokens; returns (B,
        max_new_tokens) numpy tokens."""
        ex = self.executor
        tokens = jnp.asarray(np.asarray(prompts), jnp.int32)
        last, kv, pos = ex.prefill(tokens)
        gen, _ = ex.decode(greedy_token(last), kv, pos,
                           steps=max_new_tokens)
        return gen

    def serve(self, requests: List[Request],
              max_batch: Optional[int] = None, fused: Optional[bool] = None,
              max_iterations: int = 10_000):
        """Continuous batching through the session's executor. Repeated
        calls reuse the same batcher (``None`` args keep its build-time
        configuration), so a paused serve (``max_iterations``) can be
        resumed — across ``update_budget`` swaps — without losing
        in-flight slots."""
        b = self.batcher(max_batch=max_batch, fused=fused)
        return b.serve(requests, max_iterations=max_iterations)

    def gateway(self, **kw):
        """An OpenAI-compatible async serving gateway over this session
        (DESIGN.md §13). Keyword args pass through to ``Gateway`` —
        admission queue bound, rate limits, queue-aware tier hints."""
        from repro.gateway.server import Gateway   # avoid import cycle
        return Gateway(session=self, **kw)

    # ------------------------------------------------------------ re-plan
    def update_budget(self, new_budget_bytes: int) -> ScheduleDiff:
        """Re-plan under a new VRAM/HBM budget and apply the delta live
        (DESIGN.md §8). Returns the ``Schedule.diff`` whose pin/evict bytes
        are exactly what the executor moved."""
        return self._replan(budget_bytes=new_budget_bytes)

    def update_setting(self, **changes) -> ScheduleDiff:
        """Re-plan under changed inference conditions (batch, context,
        dtypes — any ``InferenceSetting`` field) and apply the delta live."""
        return self._replan(setting=replace(self.setting, **changes))

    def _refresh_routing_stats(self):
        """Fold the executor's online routing EMA back into the profile DB
        and the expert shards' ``hot`` metadata, so the NEXT plan pins the
        observed hot set rather than the seeded one (DESIGN.md §9)."""
        if not self.expert_granular or self._executor is None:
            return
        ema = self._executor.expert_ema
        if not ema:
            return
        for layer, freqs in ema.items():
            self.db.set_routing(self.cfg.name, layer, freqs)
        for s in self.subs:
            if s.kind == "moe_expert" and s.layer in ema:
                s.meta["hot"] = float(ema[s.layer][s.meta["expert"]])

    def _replan(self, budget_bytes: Optional[int] = None,
                setting: Optional[InferenceSetting] = None) -> ScheduleDiff:
        if budget_bytes is not None:
            self.budget_bytes = budget_bytes
        if setting is not None:
            self.setting = setting
        self._refresh_routing_stats()
        # re-check draft feasibility under the new conditions (DESIGN.md
        # §14): a shrunk budget that no longer fits the draft disables
        # speculation — the target re-plans at the FULL budget, exactly
        # the spec-free schedule — and a later growth re-enables it
        if self.spec_k > 0:
            self.draft_schedule, self.draft_carve_bytes = plan_draft_carve(
                self.budget_bytes - self._emergency_reserve_bytes,
                self.draft_subs, self.subs, self.est, self.setting,
                self.tiers)
        new = build_schedule(self.budget_bytes - self.draft_carve_bytes
                             - self._emergency_reserve_bytes,
                             self.subs, self.est, self.setting, self.tiers,
                             kv_page_size=self.kv_page_size or PAGE_SIZE)
        diff = self.schedule.diff(new)
        if self._executor is not None:
            report = self._executor.rebind(new)
            assert report["pinned_bytes"] == diff.pin_bytes \
                and report["evicted_bytes"] == diff.evict_bytes, \
                "executor rebind moved different bytes than Schedule.diff"
        if self._batcher is not None:
            self._batcher._bind_schedule(new)
            self._batcher._bind_spec(
                self.spec_decoder(self._batcher.max_batch),
                self.spec_k if self.spec_active else 0)
        self.schedule = new
        self.replan_log.append(diff)
        return diff

    # ------------------------------------------------------------ ladder
    def degrade(self, reason: str = "") -> Optional[int]:
        """Walk ONE applicable rung down the emergency-rebudget ladder
        (DESIGN.md §15) in response to an allocation failure and return
        the new level, or ``None`` when the ladder is exhausted. Rungs:

          1. ``spec_off``      — drop the draft carve (spec_k -> 0)
          2. ``expert_shrink`` — veto the colder half of the expert hot set
          3. ``tier_down``     — truncate the tier table and hold back an
                                 emergency VRAM reserve (budget // 4)
          4. ``sync``          — overlap off: the prefetch slots free and
                                 every pass runs the synchronous path

        Every rung changes only residency/overlap, never a computed value,
        so tokens stay bit-identical (the per-rung arguments live in §15).
        Rungs that are no-ops for this session (dense model, spec already
        off, ...) are skipped without being reported as progress."""
        while self.degradation_level < len(DEGRADATION_RUNGS) - 1:
            nxt = self.degradation_level + 1
            rung = DEGRADATION_RUNGS[nxt]
            applied = getattr(self, f"_rung_{rung}")()
            self.degradation_level = nxt
            if applied:
                self.degrade_log.append({"level": nxt, "rung": rung,
                                         "reason": reason})
                return nxt
        return None

    def _rung_spec_off(self) -> bool:
        if self.spec_k <= 0:
            return False
        # _replan only re-carves while spec_k > 0, so the draft state must
        # be cleared here or the stale carve would keep shrinking the plan
        self.spec_k = 0
        self.draft_schedule = None
        self.draft_carve_bytes = 0
        self._replan()
        return True

    def _rung_expert_shrink(self) -> bool:
        if not self.expert_granular:
            return False
        cands = sorted((s for s in self.subs if s.kind == "moe_expert"
                        and not s.meta.get("pin_veto")),
                       key=lambda s: s.meta.get("hot", 0.0))
        if len(cands) < 2:
            return False
        for s in cands[:len(cands) // 2]:
            s.meta["pin_veto"] = True
        self._replan()
        return True

    def _rung_tier_down(self) -> bool:
        ts = tuple(sorted(self.tiers))
        cap = max(ts[0], ts[-1] // 4)
        new = tuple(t for t in ts if t <= cap)
        reserve = self.budget_bytes // 4
        if new == ts and reserve <= self._emergency_reserve_bytes:
            return False
        self.tiers = new
        self._emergency_reserve_bytes = max(reserve,
                                            self._emergency_reserve_bytes)
        self._replan()
        return True

    def _rung_sync(self) -> bool:
        ex = self._executor
        applied = False
        if ex is not None:
            if ex.prefetch is not None and not ex.stats.degraded_sync:
                ex.stats.degraded_sync = True
                applied = True
        elif self.overlap:
            applied = True
        self.overlap = False
        return applied

    def note_executor_degraded(self):
        """Record a watchdog-forced sync degrade (DESIGN.md §15): the
        executor flipped itself to the synchronous path after a prefetch
        worker death — pin the session at the terminal rung so stats()
        and the gateway's /healthz report it. Idempotent."""
        terminal = len(DEGRADATION_RUNGS) - 1
        if self.degradation_level >= terminal:
            return
        self.degradation_level = terminal
        self.overlap = False
        self.degrade_log.append({"level": terminal, "rung": "sync",
                                 "reason": "prefetch worker watchdog"})

    def degradation(self) -> dict:
        """Current ladder position + fault/recovery counters (DESIGN.md
        §15) — what ``stats()`` embeds and the gateway's /healthz and
        /metrics surface."""
        out = {"level": self.degradation_level,
               "rung": DEGRADATION_RUNGS[self.degradation_level],
               "log": list(self.degrade_log)}
        if self._executor is not None:
            ex = self._executor.stats
            out.update({
                "copy_retries": ex.fault_copy_retries,
                "copy_failures": ex.fault_copy_failures,
                "worker_crashes": ex.fault_worker_crashes,
                "demand_timeouts": ex.fault_demand_timeouts,
                "sync_fallbacks": ex.fault_sync_fallbacks,
                "alloc_failures": ex.fault_alloc_failures,
                "degraded_sync": ex.degraded_sync,
            })
        if self.faults is not None:
            out["injected"] = self.faults.counters()
        return out

    @property
    def effective_prefill_mode(self) -> str:
        """The mode the executor's prefill actually runs (the stored knob
        resolved through the executor's own rule, DESIGN.md §10)."""
        from repro.core.executor import resolve_prefill_mode
        return resolve_prefill_mode(self.prefill_mode, self.jit_engine)

    # ------------------------------------------------------------ estimates
    def estimates(self, isl: Optional[int] = None,
                  prefix_hit_frac: float = 0.0) -> dict:
        """Planner-side TTFT/TPS estimates for the bound conditions. The
        TTFT model follows the session's prefill mode — a chunk-major
        session must not advertise the layer-major 1x-stream TTFT.
        ``prefix_hit_frac`` feeds the paged prefix-cache term of the TTFT
        model (DESIGN.md §12); it only makes sense on a paged session."""
        if prefix_hit_frac and self.kv_layout != "paged":
            raise ValueError("prefix_hit_frac needs kv_layout='paged' — the "
                             "stacked cache has no prefix cache")
        isl = isl if isl is not None else self.setting.context
        out = {"ttft_s": estimate_ttft(self.schedule, isl,
                                       mode=self.effective_prefill_mode,
                                       prefix_hit_frac=prefix_hit_frac),
               "tps": estimate_tps(self.schedule, self.setting.batch),
               "pinned_bytes": self.schedule.pinned_bytes,
               "scratch_bytes": self.schedule.scratch_bytes,
               "kv_pool_bytes": self.schedule.kv_pool_bytes}
        if self.spec_active:
            # acceptance -> TPS model (DESIGN.md §14): the draft step is
            # one pinned decode iteration of its own schedule; the
            # observed accept rate (or the 0.7 prior before any serving)
            # feeds the truncated-geometric expectation, and choose_spec_k
            # reports the window the model itself would pick — k=0 when
            # the draft cannot beat plain decode
            batch = self.setting.batch
            draft_step_s = self.draft_schedule.time_for_tokens(batch)
            a = self._observed_accept_rate(default=0.7)
            out["spec"] = {
                "spec_k": self.spec_k,
                "draft_carve_bytes": self.draft_carve_bytes,
                "draft_step_s": draft_step_s,
                "accept_rate": a,
                "spec_tps": estimate_spec_tps(self.schedule, draft_step_s,
                                              a, self.spec_k, batch),
                "chosen_k": choose_spec_k(self.schedule, draft_step_s, a,
                                          batch=batch),
            }
        return out

    def _observed_accept_rate(self, default: float = 0.7) -> float:
        """The executor's measured acceptance rate, or ``default`` before
        any speculative iteration ran."""
        if self._executor is not None \
                and self._executor.stats.spec_drafted > 0:
            return self._executor.stats.accept_rate
        return default

    def stats(self) -> dict:
        """Lifecycle stats: planning + (if built) executor + batcher."""
        out = {"budget_bytes": self.budget_bytes,
               "system": self.system.name,
               "replans": len(self.replan_log),
               "weight_quant": self.cfg.weight_quant,
               "pinned_bytes": self.schedule.pinned_bytes,
               "scratch_bytes": self.schedule.scratch_bytes,
               "kv_layout": self.kv_layout,
               "kv_pool_bytes": self.schedule.kv_pool_bytes,
               # speculation state (DESIGN.md §14): requested window, live
               # feasibility under the current budget, and the carve the
               # draft's pinned residency takes out of the target's plan
               "spec_k": self.spec_k,
               "spec_active": self.spec_active,
               "draft_carve_bytes": self.draft_carve_bytes}
        if self._executor is not None:
            # device bytes by owner, counted from the arrays themselves
            # (the plan's pinned set, the outputs held beside it, the
            # batcher's KV cache, scratch and at-use high-waters)
            out["hbm_bytes"] = self._executor.hbm_bytes(
                kv=self._batcher.kv if self._batcher is not None else None)
            ex = self._executor.stats
            pf = ex.prefill_stats
            out["executor"] = {
                "streamed_bytes": ex.streamed_bytes,
                # per-storage-format split of the same bytes (DESIGN.md §11)
                "streamed_bytes_by_dtype": dict(ex.streamed_bytes_by_dtype),
                "staged_bytes": ex.staged_bytes,
                "engine_calls": dict(ex.engine_calls),
                "copy_s_hidden": ex.copy_s_hidden,
                "copy_s_exposed": ex.copy_s_exposed,
                # prefill loop-order accounting (DESIGN.md §10): passes per
                # prompt (layer-major: 1), streamed bytes per prompt (1x
                # the plan vs chunk-major's Cx) and the per-prefill
                # hidden/exposed copy split behind bench_figure2's TTFT
                "prefill_passes": ex.prefill_passes,
                "prefills": len(pf),
                # per-prefill "streamed_bytes" already folds the demanded
                # expert bytes in (executor invariant: streamed == static
                # plan + demanded)
                "prefill_streamed_bytes_per_prompt": (
                    float(np.mean([p["streamed_bytes"] for p in pf]))
                    if pf else 0.0),
                "prefill_copy_s_hidden": sum(p["copy_s_hidden"]
                                             for p in pf),
                "prefill_copy_s_exposed": sum(p["copy_s_exposed"]
                                              for p in pf),
                "prefill_stats": list(pf),
                "rebinds": ex.rebinds,
                "rebind_pinned_bytes": ex.rebind_pinned_bytes,
                "rebind_evicted_bytes": ex.rebind_evicted_bytes,
                "rebind_s": ex.rebind_s,
                # dense FFN calls by path: "jnp" or "pallas_<format>"
                "ffn_paths": (dict(self._executor.engine.ffn_paths)
                              if self._executor.engine is not None else {}),
            }
            if self.expert_granular:
                out["executor"].update({
                    "expert_hit_rate": ex.expert_hit_rate,
                    "expert_demanded": ex.expert_demanded,
                    "demanded_expert_bytes": ex.demanded_expert_bytes,
                    "resident_expert_bytes": ex.resident_expert_bytes,
                })
            if self.kv_layout == "paged":
                # page restores are the second demand-streamable shard kind
                # beside cold experts (DESIGN.md §12); same ledger bucket
                out["executor"].update({
                    "page_faults": ex.page_faults,
                    "demanded_page_bytes": ex.demanded_page_bytes,
                })
        out["degradation"] = self.degradation()
        if self._batcher is not None:
            out["serving"] = self._batcher.stats()
        return out

    # ------------------------------------------------------------ lifecycle
    def close(self):
        """Drop executor/batcher references (device arrays become
        collectable); the session stays usable for planning."""
        self._batcher = None
        self._batcher_cfg = None
        self._executor = None

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc):
        self.close()
        return False
