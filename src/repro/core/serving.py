"""Request-level serving loop (paper inference phase, Step 3/4).

The paper's scheduler is *generic over batches*: each iteration a batch may
contain context-phase chunks of newly admitted requests and one new token
per decode-phase request. The batch-wide new-token count picks the tier
(``PickTier``), whose schedule is set up and executed for everyone at once.

``ContinuousBatcher`` implements that loop over the two-tier executor:
admit -> chunked prefill at the tier size -> fused batched decode -> retire.

Decode is *fused* by default (DESIGN.md §7): one jitted multi-slot step per
iteration takes the stacked ``(L, B, KV, S, hd)`` caches, a per-slot
position vector and the batch of last tokens, and advances every active
slot at once — so each streamed sub-layer crosses the link exactly once per
iteration regardless of how many slots are in flight. ``fused=False`` keeps
the per-slot loop (one B=1 pass per active slot, which re-streams weights
per slot) as the baseline the bit-identity tests and ``bench_serving``
compare against.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.executor import PipelinedExecutor
from repro.core.faults import AllocationFault
from repro.core.kvpaged import PagedKVCache, PagePoolFull
from repro.core.planner import Schedule
from repro.models.common import greedy_token


@partial(jax.jit, static_argnums=(0, 1))
def _greedy(rule, last_only: bool, logits):
    """A greedy pick as one executable per logits shape: eager, the slice
    and the casts around the argmax dispatch ~8 tiny executables. The rule
    is a static argument, this module's ``greedy_token`` looked up at each
    call, so the f32 upcast and lowest-index ties are the shared ones."""
    return rule(logits[:, -1] if last_only else logits)


def _pick_last(logits):
    """(B, T, V) logits -> (B,) greedy tokens at each row's last position."""
    return _greedy(greedy_token, True, logits)


def _pick_all(logits):
    """(B, W, V) logits -> (B, W) greedy tokens at every position."""
    return _greedy(greedy_token, False, logits)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int
    submitted_at: float = field(default_factory=time.perf_counter)
    # filled during serving
    generated: list = field(default_factory=list)
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    cancelled_at: Optional[float] = None
    error: Optional[str] = None   # set when servicing this request failed
    pos: int = 0

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token, or ``None`` while no token has been
        emitted yet (the old ``(first_token_at or 0) - submitted_at``
        returned a large negative number for unstarted requests, which
        silently poisoned any mean over a mixed wave)."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def done(self):
        return len(self.generated) >= self.max_new_tokens


@dataclass
class TokenEvent:
    """One token emitted by one serve iteration (DESIGN.md §13): what an
    incremental caller — the gateway's SSE fan-out — receives from
    ``ContinuousBatcher.step()`` instead of waiting for the batch to
    finish. ``index`` is the token's position in ``request.generated``;
    ``done`` marks the request's final token (its slot is already free).
    ``error`` (DESIGN.md §15) marks a per-request failure event instead of
    a token: ``token`` is -1, ``done`` is True, and only this rid's client
    is affected — the other slots keep streaming."""
    rid: int
    token: int
    index: int
    done: bool
    error: Optional[str] = None


def random_requests(vocab: int, n: int, prompt_len: int,
                    max_new_tokens: int, seed: int = 0,
                    rid_base: int = 0) -> List["Request"]:
    """Uniform-random request batch (the shape every demo/benchmark wave
    uses): ``n`` requests of ``prompt_len`` int32 tokens drawn from a
    seeded RNG, so identically-parameterised waves are comparable
    token-for-token across runs and budgets."""
    rng = np.random.RandomState(seed)
    return [Request(rid=rid_base + i,
                    prompt=rng.randint(0, vocab, size=prompt_len)
                    .astype(np.int32), max_new_tokens=max_new_tokens)
            for i in range(n)]


class ContinuousBatcher:
    """Serves a stream of requests under a pipelined-sharding schedule.

    Decode slots are fixed at ``max_batch`` (the executor KV layout); new
    requests are admitted into free slots and prefilled with the
    tier-chunked schedule while existing slots keep decoding.
    """

    def __init__(self, cfg, params, schedule: Schedule = None,
                 max_batch: int = 4, max_seq: int = 256, fused: bool = True,
                 overlap: bool = True, jit_engine: bool = True,
                 executor: Optional[PipelinedExecutor] = None,
                 session=None, prefill_mode: Optional[str] = None,
                 kv_layout: str = "stacked",
                 kv_page_size: Optional[int] = None,
                 kv_pool_pages: Optional[int] = None,
                 spec=None, spec_k: int = 0):
        self.cfg = cfg
        self._session = session
        if executor is not None:
            # constructor-from-session path (DESIGN.md §8): share a live
            # executor instead of building one, so a Session can rebind the
            # schedule under this batcher without dropping its KV slots.
            # A conflicting explicit prefill_mode raises instead of being
            # silently ignored (same contract as Session.batcher's
            # max_batch/fused) — the shared executor's default governs;
            # per-call overrides go through executor.prefill(prefill_mode=)
            if prefill_mode is not None \
                    and prefill_mode != executor.prefill_mode:
                raise ValueError(
                    f"batcher executor runs prefill_mode="
                    f"{executor.prefill_mode!r}; cannot build with "
                    f"{prefill_mode!r} (set it on the Session/executor)")
            if kv_layout != "stacked" and kv_layout != executor.kv_layout:
                raise ValueError(
                    f"batcher executor runs kv_layout="
                    f"{executor.kv_layout!r}; cannot build with "
                    f"{kv_layout!r} (set it on the Session/executor)")
            self.ex = executor
            self.schedule = executor.schedule
            self.max_seq = executor.max_seq
            jit_engine = executor.engine is not None
        else:
            self.schedule = schedule
            self.max_seq = max_seq
            self.ex = PipelinedExecutor(cfg, params, schedule,
                                        max_seq=max_seq, overlap=overlap,
                                        jit_engine=jit_engine,
                                        prefill_mode=prefill_mode,
                                        kv_layout=kv_layout,
                                        kv_page_size=kv_page_size,
                                        kv_pool_pages=kv_pool_pages)
        self.max_batch = max_batch
        # the fused step runs through the jitted engine's batched decode
        self.fused = fused and jit_engine
        # speculative decoding (DESIGN.md §14): a SpecDecoder drafting
        # spec_k tokens per iteration for the fused verify pass; spec_k=0
        # (or spec None) keeps every iteration byte-identical to today
        self.spec = spec
        self.spec_k = spec_k if spec is not None and self.fused else 0
        self.kv = self.ex.init_kv(max_batch)
        # paged KV (DESIGN.md §12): admissions map pages and look up the
        # prefix cache inside executor.prefill; retire unmaps the slot
        self._paged = isinstance(self.kv, PagedKVCache)
        self.slots: List[Optional[Request]] = [None] * max_batch
        # admission queue OUTLIVES serve() calls: a paused serve (relative
        # max_iterations) may return before every request found a free
        # slot, and the resume call — serve([]) after a rebudget — must
        # still admit them
        self.pending: List[Request] = []
        # per-step emitted tokens (DESIGN.md §13): _prefill_slot/_advance
        # append here; step() drains the buffer to its caller
        self._events: List[TokenEvent] = []
        self.cancelled: List[Request] = []
        # live queue-pressure hints for the tier picks (DESIGN.md §13):
        # off until set_queue_pressure opts in — the default serve path
        # keeps every pick byte-identical to the queue-blind baseline
        self._queue_aware = False
        self._queue_depth = 0
        self._slack_s: Optional[float] = None
        # each slot's last token, on the host: a commit is a host write and
        # a pass uploads one copy (_tokens_dev), so no token costs a launch
        self.last_tokens = np.zeros((max_batch, 1), np.int32)
        self.iterations = 0
        self.tier_log = []
        self.completed: List[Request] = []
        # per-request error isolation + degradation ladder (DESIGN.md §15):
        # a request whose servicing raises is failed ALONE (its client gets
        # an error event, the other slots keep streaming); an allocation
        # failure instead walks the owning session down the rebudget ladder
        # and re-runs the pass — both logs stay empty on a clean serve
        self.failed: List[Request] = []
        self.degradations: List[dict] = []
        # per decode iteration: plan-accounted streamed weight bytes, and
        # actual host->device bytes moved (covers CPU-engine at-use fetches
        # too, which is what the per-slot baseline mostly pays at tier 1)
        self.iter_streamed_bytes: List[int] = []
        self.iter_moved_bytes: List[int] = []
        self._serve_wall_s = 0.0
        self.rebudget_log: List[dict] = []

    # ------------------------------------------------------------ session
    @classmethod
    def from_session(cls, session, max_batch: int = 4, fused: bool = True):
        """Batcher over a Session's live executor (DESIGN.md §8): the
        session owns install/planning and can re-plan under it
        (``session.update_budget`` / ``batcher.rebudget``) — in-flight
        decode slots survive because the executor only swaps pinned
        weights, never the KV stacks this batcher holds."""
        return cls(session.cfg, None, max_batch=max_batch, fused=fused,
                   executor=session.executor, session=session,
                   spec=session.spec_decoder(max_batch),
                   spec_k=session.spec_k)

    def rebudget(self, new_budget_bytes: int):
        """Re-plan the session under a new VRAM budget between iterations
        (the IGI mid-session memory-pressure scenario, DESIGN.md §8).
        Returns the applied ``ScheduleDiff``; generated tokens are
        unaffected — only weight residency (and thus per-pass transfer
        traffic) changes."""
        if self._session is None:
            raise RuntimeError("rebudget() needs a session-backed batcher "
                               "(ContinuousBatcher.from_session)")
        diff = self._session.update_budget(new_budget_bytes)
        self.rebudget_log.append({"iteration": self.iterations,
                                  "budget_bytes": new_budget_bytes,
                                  "diff": diff})
        return diff

    def _bind_schedule(self, schedule: Schedule):
        """Adopt a re-planned schedule (called by the owning Session after
        the executor rebind; tier picks from the next iteration use it)."""
        self.schedule = schedule

    def _bind_spec(self, spec, spec_k: int):
        """Adopt the session's re-checked speculation state after a
        rebudget (DESIGN.md §14): a shrunk budget that no longer fits the
        draft disables speculation mid-serve — the next iteration falls
        back to plain fused decode, bit-identically — and a later growth
        can re-enable it against the still-live draft KV."""
        self.spec = spec
        self.spec_k = spec_k if spec is not None and self.fused else 0

    # ------------------------------------------------------------ admit
    def _admit(self, queue: List[Request]):
        for i in range(self.max_batch):
            if self.slots[i] is None and queue:
                req = queue.pop(0)
                # validate BEFORE taking the slot: a rejected request must
                # not occupy it (a caller catching the ValueError and
                # serving on would otherwise decode the slot against an
                # unwritten KV cache)
                self._validate(req)
                self.slots[i] = req
                with TraceAnnotation("serving.admit", rid=req.rid,
                                     prompt_len=len(req.prompt)):
                    self._prefill_guard(i, req)

    def _validate(self, req: Request):
        T = len(req.prompt)
        if T == 0:
            raise ValueError(f"request {req.rid} has an empty prompt")
        if T + req.max_new_tokens > self.max_seq:
            # past max_seq the cache write offset clamps and the validity
            # mask saturates — silently wrong tokens, so reject up front
            raise ValueError(
                f"request {req.rid}: prompt ({T}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_seq ({self.max_seq})")

    def _prefill_slot(self, slot: int, req: Request):
        """Chunked prefill of one request through the executor's prefill
        path (layer-major weight-stationary by default, DESIGN.md §10)
        against the shared KV slot: each streamed sub-layer crosses the
        link once per admitted prompt, not once per chunk."""
        T = len(req.prompt)
        tokens = jnp.asarray(req.prompt, jnp.int32)[None, :]
        n_tiers = len(self.ex.stats.tiers_used)
        if self._paged:
            # paged admission maps pages instead of slicing the slot; the
            # prefix-cache lookup runs inside executor.prefill
            logits, _, _ = self.ex.prefill(tokens, kv=self.kv, slot=slot)
        elif self.ex.engine is not None \
                and self.ex.prefill_mode == "layer_major":
            # slot-threaded donated path (DESIGN.md §12): the jitted step
            # slices and writes the slot row in place of the old
            # serving-side `.at[:, slot:slot+1].set(...)`, which
            # materialised a full-cache copy per admission
            logits, self.kv, _ = self.ex.prefill(tokens, kv=self.kv,
                                                 slot=slot)
        else:
            # chunk-major / eager baseline: slice the slot out and back
            kv_slot = {
                "k": self.kv["k"][:, slot:slot + 1],
                "v": self.kv["v"][:, slot:slot + 1],
            }
            logits, kv_slot, _ = self.ex.prefill(tokens, kv=kv_slot)
            self.kv["k"] = self.kv["k"].at[:, slot:slot + 1].set(kv_slot["k"])
            self.kv["v"] = self.kv["v"].at[:, slot:slot + 1].set(kv_slot["v"])
        self.tier_log.extend(self.ex.stats.tiers_used[n_tiers:])
        if self.spec is not None:
            # warm the draft's KV slot alongside the target's (DESIGN.md
            # §14); kept even while spec_k is 0 (rebudget-disabled) so a
            # later re-enable finds the prompt prefix in place
            self.spec.prefill_slot(slot, req.prompt)
        with TraceAnnotation("serving.sample"):
            nxt = int(np.asarray(_pick_last(logits))[0])
            req.generated.append(nxt)
            req.first_token_at = time.perf_counter()
            req.pos = T
            self.last_tokens[slot, 0] = nxt
            self._events.append(TokenEvent(req.rid, nxt,
                                           len(req.generated) - 1, req.done))
            # a request whose budget is a single token finishes on its
            # prefill token: retire it here so its slot frees immediately
            # and done_at is recorded exactly like a decode-phase completion
            if req.done:
                self._retire(slot)

    def _prefill_guard(self, slot: int, req: Request):
        """Admission under fault protection (DESIGN.md §15). An allocation
        failure (injected ``alloc.device``/``alloc.host`` or a real
        ``PagePoolFull``) walks the session down the degradation ladder and
        re-runs the prefill — after unmapping any pages the failed attempt
        already attached, since ``prefix_attach`` asserts on remapping an
        occupied slot. Any other exception fails THIS request only: its
        client gets an error event and the slot frees; the other slots'
        KV rows never moved, so their tokens are bit-identical to an
        undisturbed run. ``ValueError`` (contract violations) still
        propagates — misconfiguration is the operator's bug, not the
        request's."""
        while True:
            try:
                if self.ex.faults is not None:
                    self.ex.faults.check("serving.request", key=str(req.rid))
                self._prefill_slot(slot, req)
                return
            except (AllocationFault, PagePoolFull) as e:
                if self._paged:
                    self.kv.free_slot(slot)
                self._degrade_or_raise(e)
            except ValueError:
                raise
            except Exception as e:
                self._fail_slot(slot, e)
                return

    def _fail_slot(self, slot: int, exc: Exception):
        """Fail ONE in-flight request (DESIGN.md §15): record the error,
        free the slot (and its paged blocks), and emit a terminal error
        event so the gateway can 500 exactly this client."""
        req = self.slots[slot]
        req.error = str(exc) or type(exc).__name__
        req.done_at = time.perf_counter()
        self.failed.append(req)
        self.slots[slot] = None
        if self._paged:
            self.kv.free_slot(slot)
        self._events.append(TokenEvent(req.rid, -1, len(req.generated),
                                       True, error=req.error))

    def _degrade_or_raise(self, exc: Exception):
        """Step the owning session one rung down the degradation ladder
        (DESIGN.md §15) in response to an allocation failure, or re-raise
        when there is no session / the ladder is exhausted."""
        if self._session is None:
            raise exc
        level = self._session.degrade(reason=str(exc))
        if level is None:
            raise exc
        self.degradations.append({"iteration": self.iterations,
                                  "level": level, "reason": str(exc)})

    def _tokens_dev(self, rows: slice = slice(None)):
        """Upload ``last_tokens[rows]`` for one pass. The copy keeps the
        upload from aliasing the host buffer, which the next commit writes
        while the pass may still run."""
        return jax.device_put(self.last_tokens[rows].copy())

    def _run_slot(self, slot: int, tokens, pos):
        """Runs a single-sequence chunk against the shared KV slot. The
        executor's caches are stacked (L, B, KV, S, hd) arrays, so slot
        extraction/write-back is a single slice on the batch axis."""
        kv_slot = {
            "k": self.kv["k"][:, slot:slot + 1],
            "v": self.kv["v"][:, slot:slot + 1],
        }
        logits, kv_slot = self.ex._run_chunk(tokens, kv_slot, pos)
        self.kv["k"] = self.kv["k"].at[:, slot:slot + 1].set(kv_slot["k"])
        self.kv["v"] = self.kv["v"].at[:, slot:slot + 1].set(kv_slot["v"])
        self.tier_log.append(self.schedule.pick_tier(tokens.shape[0]
                                                     * tokens.shape[1]))
        return logits

    # ------------------------------------------------------------ retire
    def _retire(self, slot: int):
        req = self.slots[slot]
        req.done_at = time.perf_counter()
        self.completed.append(req)
        self.slots[slot] = None
        if self._paged:
            # unmap the sequence's pages; prefix-cached blocks survive
            # through the cache's own reference (DESIGN.md §12)
            self.kv.free_slot(slot)

    # ------------------------------------------------------------ decode
    def _decode_iteration(self):
        """One batched decode step for every active slot (batch-wide new
        token count = #active -> tier table drives the schedule)."""
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return
        before = self.ex.stats.streamed_bytes
        moved_before = self.ex.stats.staged_bytes
        with TraceAnnotation("serving.decode", active=len(active)):
            if self.spec_k > 0:
                self._decode_spec(active)
            elif self.fused:
                self._decode_fused(active)
            else:
                self._decode_per_slot(active)
        self.iter_streamed_bytes.append(self.ex.stats.streamed_bytes - before)
        self.iter_moved_bytes.append(self.ex.stats.staged_bytes
                                     - moved_before)

    def _decode_fused(self, active: List[int]):
        """Fused multi-slot step: every active slot advances one token in a
        single batched pass; streamed sub-layers are fetched once for the
        whole iteration (DESIGN.md §7)."""
        pos_vec = np.zeros((self.max_batch,), np.int32)
        mask = np.zeros((self.max_batch,), bool)
        for i in active:
            pos_vec[i] = self.slots[i].pos
            mask[i] = True
        self.tier_log.append(self.schedule.pick_decode_tier(
            len(active), queue_depth=self.ex.sched_queue_depth,
            slack_s=self.ex.sched_slack_s))
        logits, self.kv = self.ex._run_decode(
            self._tokens_dev(), self.kv, jnp.asarray(pos_vec),
            jnp.asarray(mask), n_active=len(active))
        with TraceAnnotation("serving.sample"):
            nxt = np.asarray(_pick_last(logits))
            for i in active:
                self._advance_guard(i, int(nxt[i]))

    def _seq_token(self, req: Request, idx: int) -> int:
        """Committed sequence token at index ``idx``: prompt positions
        first, then generated tokens (generated[0] sits at position
        len(prompt) — the prefill-produced token)."""
        T = len(req.prompt)
        if idx < T:
            return int(req.prompt[idx])
        return int(req.generated[idx - T])

    def _decode_spec(self, active: List[int]):
        """One speculative iteration (DESIGN.md §14): draft ``k`` greedy
        tokens per active slot on the pinned draft, verify all ``k+1``
        positions in ONE streamed target pass, commit the longest
        accepted prefix plus the target's bonus token, roll back the
        rejected KV suffix. Longest-prefix greedy acceptance makes every
        committed token the target's own argmax over an identical
        context, so the output is bit-identical to plain greedy decode
        by construction.

        The window is clamped so every active slot's writes stay inside
        the cache (``pos + W <= max_seq`` — ``dynamic_update_slice``
        would clamp the start index and corrupt earlier positions
        otherwise); near the sequence end the iteration degrades to a
        plain fused step."""
        W = min(self.spec_k + 1,
                self.max_seq - max(self.slots[i].pos for i in active))
        if W < 2:
            self._decode_fused(active)
            return
        k = W - 1
        B = self.max_batch
        pos_vec = np.zeros((B,), np.int32)
        mask = np.zeros((B,), bool)
        prev_tok = np.zeros((B,), np.int32)
        for i in active:
            r = self.slots[i]
            pos_vec[i] = r.pos
            mask[i] = True
            prev_tok[i] = self._seq_token(r, r.pos - 1)
        last = self.last_tokens.reshape(-1).copy()
        drafts = self.spec.draft(prev_tok, last, pos_vec, mask, k,
                                 n_active=len(active))
        tokens = np.concatenate([last[:, None], drafts],
                                axis=1).astype(np.int32)
        # a verify pass IS a batch-wide new-token count of n_active * W
        # in the paper's PickTier sense — log the same pick _run_verify
        # makes so tier accounting matches plain serving's convention
        self.tier_log.append(self.schedule.pick_decode_tier(
            len(active) * W, queue_depth=self.ex.sched_queue_depth,
            slack_s=self.ex.sched_slack_s))
        logits, self.kv = self.ex._run_verify(
            jnp.asarray(tokens), self.kv, jnp.asarray(pos_vec),
            jnp.asarray(mask), n_active=len(active))
        with TraceAnnotation("serving.sample"):
            targets = np.asarray(_pick_all(logits))  # (B, W)
            keep_pos = np.zeros((B,), np.int32)
            roll_mask = np.zeros((B,), bool)
            st = self.ex.stats
            for i in active:
                r = self.slots[i]
                # longest accepted draft prefix: d_{j+1} == target's greedy
                # continuation t_j over the identical committed context
                a = 0
                while a < k and drafts[i, a] == targets[i, a]:
                    a += 1
                remaining = r.max_new_tokens - len(r.generated)
                e = min(a + 1, remaining)
                st.spec_drafted += k
                st.spec_accepted += e - 1  # bonus token not counted
                for j in range(e):
                    if self.slots[i] is None:
                        # _advance_guard failed the slot mid-commit — the
                        # remaining accepted tokens die with the request
                        break
                    self._advance_guard(i, int(targets[i, j]))
                if e < W:
                    st.spec_rollbacks += 1
                    st.spec_rolled_back_tokens += W - e
                    if self.slots[i] is not None:
                        keep_pos[i] = pos_vec[i] + e
                        roll_mask[i] = True
                    # a retired slot needs no rollback: paged free_slot
                    # already released its blocks; a stacked slot's stale
                    # tail is masked until the next admission overwrites it
        if roll_mask.any():
            self.kv = self.ex.rollback_kv(self.kv, keep_pos, roll_mask)

    def _decode_per_slot(self, active: List[int]):
        """Baseline: slots decode one at a time, paying the streamed-weight
        copy once per active slot per iteration, each pass at the tier
        picked for its single new token. With the jitted engine each slot
        runs a one-hot-masked pass at the full batch shape — the same
        executables as the fused step, so on backends where both paths use
        the same FFN kernel (any CPU run, incl. CI) the comparison is
        bitwise; on TPU the fused iteration's tier may mark FFNs streamed
        and route them through the Pallas ``streamed_matmul`` kernel, which
        is allclose- but not bit-equal. The eager engine falls back to the
        seed's B=1 slice loop."""
        if self.ex.engine is None:
            for i in active:
                logits = self._run_slot(i, self._tokens_dev(slice(i, i + 1)),
                                        self.slots[i].pos)
                self._advance_guard(i, int(np.asarray(_pick_last(logits))[0]))
            return
        pos_vec = np.zeros((self.max_batch,), np.int32)
        for i in active:
            pos_vec[i] = self.slots[i].pos
        pos_vec = jnp.asarray(pos_vec)
        for i in active:
            mask = np.zeros((self.max_batch,), bool)
            mask[i] = True
            self.tier_log.append(self.schedule.pick_decode_tier(
                1, queue_depth=self.ex.sched_queue_depth,
                slack_s=self.ex.sched_slack_s))
            logits, self.kv = self.ex._run_decode(
                self._tokens_dev(), self.kv, pos_vec, jnp.asarray(mask),
                n_active=1)
            self._advance_guard(i, int(np.asarray(_pick_last(logits))[i]))

    def _advance_guard(self, slot: int, token: int):
        """Per-request isolation on the decode commit path (DESIGN.md §15):
        an exception servicing one slot's token — including an injected
        ``serving.request`` fault keyed to its rid — fails that request
        alone; the batched pass already ran, so the other slots commit
        their tokens untouched. Allocation failures are NOT per-request
        (the ladder in ``step`` handles them) and re-raise."""
        try:
            if self.ex.faults is not None:
                req = self.slots[slot]
                self.ex.faults.check("serving.request", key=str(req.rid))
            self._advance(slot, token)
        except (AllocationFault, PagePoolFull):
            raise
        except Exception as e:
            self._fail_slot(slot, e)

    def _advance(self, slot: int, token: int):
        req = self.slots[slot]
        req.generated.append(token)
        req.pos += 1
        self.last_tokens[slot, 0] = token
        self._events.append(TokenEvent(req.rid, token,
                                       len(req.generated) - 1, req.done))
        if req.done:
            self._retire(slot)

    # ------------------------------------------------------------ loop
    @property
    def has_work(self) -> bool:
        """True while a step would make progress (queued or in-flight)."""
        return bool(self.pending) or any(s is not None for s in self.slots)

    def submit(self, requests: List[Request]):
        """Queue requests for admission by the next step (the incremental
        caller's entry point; ``serve`` does this + loops)."""
        self.pending.extend(requests)

    def step(self) -> List[TokenEvent]:
        """ONE serve iteration — admit into free slots, run one fused
        decode pass — and return the tokens it emitted, per slot
        (DESIGN.md §13). ``serve()`` is a loop over this, bit-identically:
        an incremental caller (the gateway) interleaving other work
        between steps sees exactly the token sequences a blocking
        ``serve()`` would have produced, it just observes them per
        iteration instead of at batch completion."""
        self._events = []
        t0 = time.perf_counter()
        with TraceAnnotation("serving.step", iteration=self.iterations,
                             active=sum(s is not None for s in self.slots),
                             pending=len(self.pending)):
            if self._queue_aware:
                self._apply_queue_hints(admitting=True)
            self._admit(self.pending)
            if self._queue_aware:
                self._apply_queue_hints(admitting=False)
            while True:
                try:
                    self._decode_iteration()
                    break
                except (AllocationFault, PagePoolFull) as e:
                    # emergency-rebudget ladder (DESIGN.md §15): degrade
                    # one rung and re-run the iteration. The failed attempt
                    # aborted before its KV writes (alloc checks fire at
                    # pass entry), and a re-run writes the same tokens at
                    # the same positions, so the retry is bit-identical.
                    self._degrade_or_raise(e)
            self.iterations += 1
            if self._session is not None and self.ex.stats.degraded_sync:
                # watchdog propagation: a prefetch-worker death already
                # flipped the executor to the sync path; let the session
                # record the terminal ladder rung so stats()/metrics
                # report it
                self._session.note_executor_degraded()
        self._serve_wall_s += time.perf_counter() - t0
        return self._events

    def cancel(self, rid: int) -> Optional[str]:
        """Abandon a request mid-flight (client disconnect, DESIGN.md §13):
        a queued request leaves ``pending``; an in-flight one is retired
        WITHOUT a completion — its slot frees this instant and, under the
        paged layout, its non-shared KV blocks are deref'd so the pool
        space returns (prefix-cached blocks survive through the cache's
        own reference). Other slots are untouched: their KV rows and
        positions never move, so their remaining tokens are bit-identical
        to an undisturbed run. Returns "queued"/"active", or ``None`` when
        the rid is unknown (already completed or never submitted)."""
        for i, r in enumerate(self.pending):
            if r.rid == rid:
                self.pending.pop(i)
                r.cancelled_at = time.perf_counter()
                self.cancelled.append(r)
                return "queued"
        for slot, r in enumerate(self.slots):
            if r is not None and r.rid == rid:
                r.cancelled_at = time.perf_counter()
                self.slots[slot] = None
                if self._paged:
                    self.kv.free_slot(slot)
                self.cancelled.append(r)
                return "active"
        return None

    def set_queue_pressure(self, depth: int = 0,
                           slack_s: Optional[float] = None):
        """Feed live queue depth / deadline slack into the tier picks
        (DESIGN.md §13) and enable queue-aware scheduling for subsequent
        steps. ``depth`` is the caller's admission-queue depth BEYOND
        ``pending`` (the gateway broker's waiting line); ``slack_s`` the
        tightest deadline slack across its live requests. Each step caps
        the raw depth at what can actually join the batch (``max_batch``)
        before it reaches ``Schedule.pick_decode_tier`` /
        ``pick_prefill_tier`` through the executor's hint fields, so
        bursts step tiers up one iteration early and idle periods shrink
        them back. Never calling this keeps every pick byte-identical to
        the queue-blind baseline."""
        self._queue_aware = True
        self._queue_depth = max(0, depth)
        self._slack_s = slack_s

    def _apply_queue_hints(self, admitting: bool):
        """Resolve the raw pressure into the executor's hint fields at the
        two moments a step picks tiers. Before admissions the hint raises
        the prefill-tier floor to the imminent batch (executor floors at
        B=1 per admission); before the decode pass it is the extra rows
        the imminent batch holds beyond the currently active ones."""
        active = sum(1 for s in self.slots if s is not None)
        imminent = min(active + len(self.pending) + self._queue_depth,
                       self.max_batch)
        self.ex.sched_queue_depth = max(0, imminent - (1 if admitting
                                                       else active))
        self.ex.sched_slack_s = self._slack_s

    def serve(self, requests: List[Request], max_iterations: int = 10_000):
        """Admit + decode until the queue drains or ``max_iterations``
        iterations *of this call* have run — relative, so a paused serve on
        a live batcher (e.g. around a ``rebudget`` swap) can resume with
        ``serve([])`` and in-flight slots keep decoding. Requests that never
        reached a free slot before the pause stay in ``self.pending`` and
        are admitted by the resume call — a pause never drops work."""
        self.submit(requests)
        start = self.iterations
        while self.has_work and self.iterations - start < max_iterations:
            self.step()
        return requests

    def stats(self):
        done = self.completed
        iters = self.iter_streamed_bytes
        total_generated = sum(len(r.generated) for r in done) \
            + sum(len(r.generated) for r in self.slots if r is not None)
        out = {
            "iterations": self.iterations,
            "kv_layout": self.ex.kv_layout,
            "tiers_used": sorted(set(self.tier_log)),
            "streamed_bytes": self.ex.stats.streamed_bytes,
            "streamed_bytes_by_dtype":
                dict(self.ex.stats.streamed_bytes_by_dtype),
            "engine_calls": dict(self.ex.stats.engine_calls),
            # completion stats (satellite: serve() used to build-and-drop a
            # quadratic `done` list; the retire path now records these)
            "completed": len(done),
            "cancelled": len(self.cancelled),
            # fault handling (DESIGN.md §15): per-request failures and
            # ladder steps taken under this batcher — zero on a clean serve
            "failed": len(self.failed),
            "degradations": len(self.degradations),
            "generated_tokens": total_generated,
            "wall_s": self._serve_wall_s,
            "aggregate_tps": total_generated / max(self._serve_wall_s, 1e-12),
            # mean over requests that actually emitted a first token:
            # unfinished/never-started ones report ttft None and are
            # skipped instead of dragging the mean negative
            "mean_ttft_s": (float(np.mean(
                [r.ttft for r in done if r.ttft is not None]))
                if any(r.ttft is not None for r in done) else 0.0),
            "mean_iter_streamed_bytes": (float(np.mean(iters))
                                         if iters else 0.0),
            "mean_iter_moved_bytes": (float(np.mean(self.iter_moved_bytes))
                                      if self.iter_moved_bytes else 0.0),
            # prefill loop order (DESIGN.md §10): passes and streamed bytes
            # per admitted prompt — layer-major holds these at 1 pass / 1x
            # plan bytes regardless of chunk count
            "prefill_passes": self.ex.stats.prefill_passes,
            "mean_prefill_streamed_bytes": (
                float(np.mean([p["streamed_bytes"]
                               for p in self.ex.stats.prefill_stats]))
                if self.ex.stats.prefill_stats else 0.0),
            # live re-plans applied under this batcher (DESIGN.md §8)
            "rebudgets": len(self.rebudget_log),
            "rebind_s": self.ex.stats.rebind_s,
            # expert-granular MoE serving (DESIGN.md §9): how often the
            # routers hit the pinned hot set, and demanded-vs-resident
            # expert bytes per decode iteration
            "expert_hit_rate": self.ex.stats.expert_hit_rate,
            "expert_demanded": self.ex.stats.expert_demanded,
            "demanded_expert_bytes": self.ex.stats.demanded_expert_bytes,
            "resident_expert_bytes": self.ex.stats.resident_expert_bytes,
            # speculative decoding (DESIGN.md §14): always present — all
            # zeros when speculation is off/disabled, so dashboards need
            # no schema branch and the gateway /metrics just forwards them
            "spec_k": self.spec_k,
            "spec_drafted": self.ex.stats.spec_drafted,
            "spec_accepted": self.ex.stats.spec_accepted,
            "accept_rate": self.ex.stats.accept_rate,
            "spec_rollbacks": self.ex.stats.spec_rollbacks,
            "spec_rolled_back_tokens":
                self.ex.stats.spec_rolled_back_tokens,
            "spec_verify_passes": self.ex.stats.spec_verify_passes,
        }
        if self.spec is not None:
            out["draft"] = self.spec.stats_dict()
        if self._paged:
            # paged-KV serving (DESIGN.md §12): pool residency, fault /
            # eviction traffic and prefix-cache hits for this batch
            out["paged_kv"] = self.kv.stats_dict()
            out["page_faults"] = self.ex.stats.page_faults
            out["demanded_page_bytes"] = self.ex.stats.demanded_page_bytes
        return out
