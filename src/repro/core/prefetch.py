"""Async weight-prefetch engine: true pipelined copy-compute.

The paper's headline mechanism overlaps PCIe weight streaming with GPU
compute through a VRAM scratch double-buffer. The seed executor only
simulated it — each streamed sub-layer's weights were transferred
synchronously at point-of-use, serialising copy and compute. This engine
makes the overlap real:

- a background transfer thread walks the plan's ``static_stream_order``
  (streamed placements in execution order) and stages each sub-layer's
  weights into one of two scratch slots via ``jax.device_put``;
- slot occupancy is bounded by a semaphore sized from the schedule's
  ``scratch_bytes`` (2 slots when the budget fits a double-buffer of the
  largest streamed sub-layer, else 1 — which degrades to the synchronous
  behaviour);
- compute calls ``acquire(name)`` which blocks only if the copy has not
  finished; the measured wait is the *exposed* copy time, and
  ``copy_s - exposed`` is the *hidden* portion (the overlap win), both
  accumulated into ``PrefetchStats``;
- ``release(name)`` drops the engine's reference after compute is
  dispatched, freeing the slot so the thread can stage sub-layer i+1 while
  sub-layer i computes.

Demand streaming (DESIGN.md §9): expert-granular MoE plans cannot enqueue
their cold expert shards up front — which experts a pass needs is only
known after each layer's router runs. A session opened with
``demand_bytes > 0`` therefore runs a SECOND transfer worker over a
dynamic queue fed by ``request()`` mid-pass, with its own slot pool.
Keeping the pools separate is what makes demand fetches deadlock-free:
the static worker may already hold both static slots staging layers
*ahead* of the consumer, and a demanded expert must never have to wait
for those slots (the consumer won't release them before it gets the
expert).

Paged-KV restores (DESIGN.md §12) are the second demand-streamable shard
kind: evicted KV pages a pass touches come back through this same pool as
synthetic ``kv_page`` shards. The demand queue is FIFO and slot-bounded,
so the executor requests each layer's page faults only at that layer's
start — interleaving all layers' pages up front could park a page request
ahead of an earlier layer's expert demand the consumer is blocked on.

One session (``start``/``finish``) corresponds to one pass over a chunk's
plan; sessions are cheap (daemon threads) and keep the queues exactly in
step with the executor's consumption order.

Fault tolerance (DESIGN.md §15): stage copies retry with exponential
backoff under ``RecoveryPolicy`` before surfacing an error; ``acquire``
takes an optional deadline and raises ``DemandTimeout`` past it (the
executor then ``abandon()``s the entry and sync-fetches the shard); and
a worker thread that dies fails every pending slot of its pool with
``WorkerLost`` instead of leaving ``wait()`` callers blocked forever —
the executor's watchdog sees that error and degrades to the sync path.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.core.faults import (DemandTimeout, FaultPlan, RecoveryPolicy,
                               WorkerLost)


def tree_nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def copy_to_device(tree, nbytes: int):
    """One host-to-HBM transfer, waited on: the ``link.copy`` span, on
    whichever thread makes it (a staging worker or the consumer)."""
    with TraceAnnotation("link.copy", bytes=nbytes):
        dev = jax.device_put(tree)
        jax.block_until_ready(dev)
    return dev


@dataclass
class PrefetchStats:
    copy_s_hidden: float = 0.0   # copy time overlapped under compute
    copy_s_exposed: float = 0.0  # copy time the consumer actually waited
    staged_bytes: int = 0        # actual bytes moved host->device
    staged_sublayers: int = 0
    slots: int = 0               # realised double-buffer depth (0: no session)
    demand_slots: int = 0        # realised demand-pool depth (expert shards)
    demanded_sublayers: int = 0  # shards staged through the demand queue
    demanded_pages: int = 0      # of which: paged-KV restores (kv_page)
    copy_retries: int = 0        # stage copies retried after a failure
    copy_failures: int = 0       # stage copies that exhausted their retries
    worker_crashes: int = 0      # transfer threads that died (DESIGN.md §15)
    abandoned: int = 0           # demand entries dropped past their deadline
    scratch_peak_bytes: int = 0  # high-water of staged bytes not yet released


class _Staged:
    __slots__ = ("event", "tree", "copy_s", "error", "pool", "abandoned",
                 "holds_slot", "held_bytes")

    def __init__(self, pool: str = "static"):
        self.event = threading.Event()
        self.tree = None
        self.copy_s = 0.0
        self.error: Optional[BaseException] = None
        self.pool = pool
        self.abandoned = False
        # True once a staging worker sem.acquire()'d a scratch slot for
        # this entry — a WorkerLost-failed entry never held one, so the
        # discard/finish paths know whether a release is owed
        self.holds_slot = False
        # staged bytes this entry counts in the engine's scratch total
        # until it is released, discarded or abandoned
        self.held_bytes = 0


class PrefetchEngine:
    """Background-thread transfer queues over a plan's streamed placements.

    ``fetch_host(sub)`` returns the host-resident weight tree of a
    sub-layer; the engine moves it to device with ``jax.device_put`` and
    hands the device tree to ``acquire`` — in FIFO order per pool.
    """

    def __init__(self, fetch_host: Callable,
                 faults: Optional[FaultPlan] = None,
                 recovery: Optional[RecoveryPolicy] = None):
        self._fetch_host = fetch_host
        self.faults = faults
        self.recovery = recovery if recovery is not None else RecoveryPolicy()
        self.stats = PrefetchStats()
        self._thread: Optional[threading.Thread] = None
        self._demand_thread: Optional[threading.Thread] = None
        self._staged: dict = {}
        self._sem: Optional[threading.Semaphore] = None
        self._demand_sem: Optional[threading.Semaphore] = None
        self._demand_q: deque = deque()
        self._demand_cv = threading.Condition()
        self._lock = threading.Lock()  # guards _Staged event/abandoned races
        self._closed = True
        self._held_bytes = 0
        self._pass_id = 0
        self.worker_error: Optional[WorkerLost] = None
        self.demand_worker_error: Optional[WorkerLost] = None

    @property
    def active(self) -> bool:
        """True while a staging session is running. Live re-plans
        (``PipelinedExecutor.rebind``, DESIGN.md §8) must wait for the pass
        to finish: sessions size their scratch slots from the *bound*
        schedule's tier entry, so a swap mid-session would leave staged
        slots sized for the old scratch budget."""
        return self._thread is not None or self._demand_thread is not None

    # ------------------------------------------------------------ session
    @staticmethod
    def slots_for(order, avail_bytes: Optional[int]) -> int:
        """Double-buffer when the weight portion of the scratch (scratch
        minus the activation reservation) fits two of the largest streamed
        sub-layers, else degrade to a single (synchronous) slot."""
        if avail_bytes is None:
            return 2
        max_w = max((p.sub.weight_bytes for p in order), default=0)
        return 2 if avail_bytes >= 2 * max_w else 1

    def start(self, order: List, avail_bytes: Optional[int] = None,
              demand_bytes: int = 0, pass_id: int = 0):
        """Begin staging ``order`` (Placement list) one sub-layer ahead.

        Every item of ``order`` MUST be acquire()d and release()d by the
        consumer in this exact sequence (or the session finish()ed early) —
        a skipped item would hold its scratch slot for the whole pass.

        ``demand_bytes > 0`` additionally opens the session for mid-pass
        ``request()`` calls (demand-streamed expert shards, DESIGN.md §9);
        the value is the largest shard a request may carry, used to size
        the demand slot pool. ``pass_id`` tags the session's
        ``prefetch.stage`` spans with the consumer's pass.
        """
        assert not self.active, "prefetch session already active"
        if not order and demand_bytes <= 0:
            return
        names = [p.sub.name for p in order]
        assert len(set(names)) == len(names), "duplicate sub-layer in order"
        self.stats.slots = self.slots_for(order, avail_bytes)
        self._sem = threading.Semaphore(self.stats.slots)
        self._staged = {n: _Staged() for n in names}
        self._pass_id = pass_id
        self._closed = False
        self.worker_error = None
        self.demand_worker_error = None
        if demand_bytes > 0:
            # the demand pool sizes from what the STATIC slots leave of the
            # scratch allowance (the planner reserves one demand shard on
            # top of the double-buffer, DESIGN.md §9); the floor of one
            # slot mirrors the static pool's single-slot degradation
            if avail_bytes is None:
                self.stats.demand_slots = 2
            else:
                max_static = max((p.sub.weight_bytes for p in order),
                                 default=0)
                remaining = avail_bytes - self.stats.slots * max_static
                self.stats.demand_slots = \
                    2 if remaining >= 2 * demand_bytes else 1
            self._demand_sem = threading.Semaphore(self.stats.demand_slots)
            self._demand_q = deque()
            self._demand_thread = threading.Thread(
                target=self._demand_worker, daemon=True)
            self._demand_thread.start()
        else:
            self.stats.demand_slots = 0
        if order:
            self._thread = threading.Thread(
                target=self._worker, args=(list(order),), daemon=True)
            self._thread.start()

    def _stage_one(self, pl, st: _Staged):
        """Stage one shard, retrying failed copies with exponential
        backoff (DESIGN.md §15) before surfacing the error on acquire.
        Each attempt re-runs the whole fetch+put, so a retried transfer
        lands exactly once in ``staged_bytes``."""
        pol = self.recovery
        point = "demand.copy" if st.pool == "demand" else "prefetch.copy"
        st.holds_slot = True
        attempt = 0
        while True:
            try:
                with TraceAnnotation("prefetch.stage", sub=pl.sub.name,
                                     bytes=pl.sub.weight_bytes, pool=st.pool,
                                     pass_id=self._pass_id, attempt=attempt):
                    t0 = time.perf_counter()
                    if self.faults is not None:
                        self.faults.check(point, key=pl.sub.name)
                    host = self._fetch_host(pl.sub)
                    nbytes = tree_nbytes(host)
                    dev = copy_to_device(host, nbytes)
                    st.copy_s = time.perf_counter() - t0
                st.tree = dev
                self.stats.staged_bytes += nbytes
                self.stats.staged_sublayers += 1
                break
            except BaseException as e:
                if attempt >= pol.max_copy_retries or not pol.retryable(e):
                    st.error = e  # surfaced on acquire
                    self.stats.copy_failures += 1
                    break
                self.stats.copy_retries += 1
                pol.sleep(pol.backoff_s(attempt))
                attempt += 1
        with self._lock:
            st.event.set()
            if st.abandoned:  # consumer gave up past its deadline
                st.tree = None
                (self._demand_sem if st.pool == "demand"
                 else self._sem).release()
            elif st.tree is not None:
                st.held_bytes = nbytes
                self._held_bytes += nbytes
                self.stats.scratch_peak_bytes = max(
                    self.stats.scratch_peak_bytes, self._held_bytes)

    def _drop_held(self, st: _Staged):
        """Take a released entry's bytes off the scratch total (caller
        holds ``_lock``)."""
        self._held_bytes -= st.held_bytes
        st.held_bytes = 0

    def _worker(self, order):
        try:
            for pl in order:
                self._sem.acquire()
                if self.faults is not None:
                    self.faults.check("prefetch.worker", key=pl.sub.name)
                self._stage_one(pl, self._staged[pl.sub.name])
        except BaseException as e:
            self._worker_died("static", e)

    def _demand_worker(self):
        try:
            while True:
                with self._demand_cv:
                    while not self._demand_q and not self._closed:
                        self._demand_cv.wait()
                    if not self._demand_q and self._closed:
                        return
                    pl = self._demand_q.popleft()
                self._demand_sem.acquire()
                if self.faults is not None:
                    self.faults.check("demand.worker", key=pl.sub.name)
                self.stats.demanded_sublayers += 1
                if pl.sub.kind == "kv_page":
                    self.stats.demanded_pages += 1
                self._stage_one(pl, self._staged[pl.sub.name])
        except BaseException as e:
            self._worker_died("demand", e)

    def _worker_died(self, pool: str, exc: BaseException):
        """A transfer worker crashed outside the per-item staging path.
        Fail every pending unstaged slot of its pool so blocked
        ``acquire()``/``finish()`` callers wake with ``WorkerLost``
        instead of hanging forever (DESIGN.md §15); the executor's
        watchdog degrades to sync fetches at its next touchpoint. The
        dead pool's semaphore can be over-released harmlessly — each
        ``start()`` builds a fresh one."""
        err = WorkerLost(f"{pool} prefetch worker died: {exc!r}")
        err.__cause__ = exc
        self.stats.worker_crashes += 1
        with self._demand_cv:
            if pool == "demand":
                self.demand_worker_error = err
                self._demand_q.clear()
            else:
                self.worker_error = err
            with self._lock:
                for st in self._staged.values():
                    if st.pool == pool and not st.event.is_set():
                        st.error = err
                        st.event.set()

    # ------------------------------------------------------------ demand
    def request(self, placements: List):
        """Enqueue demand-streamed shards mid-pass (router-selected cold
        experts). The caller must acquire()/release() each requested shard
        before the pass finishes. Only valid on sessions started with
        ``demand_bytes > 0``."""
        assert self._demand_thread is not None, \
            "request() on a session without a demand pool"
        with self._demand_cv:
            for pl in placements:
                name = pl.sub.name
                assert name not in self._staged, \
                    f"{name} already staged/requested this pass"
                st = _Staged(pool="demand")
                if self.demand_worker_error is not None:
                    # dead demand worker: fail the entry up front rather
                    # than queueing work nobody will ever stage
                    st.error = self.demand_worker_error
                    st.event.set()
                else:
                    self._demand_q.append(pl)
                self._staged[name] = st
            self._demand_cv.notify()

    # ------------------------------------------------------------ consume
    def acquire(self, name: str, timeout: Optional[float] = None):
        """Block until ``name``'s weights are staged; returns the device
        tree. The wait is the exposed copy time; the rest was hidden.
        With ``timeout``, a miss raises ``DemandTimeout`` — the caller
        must then ``abandon(name)`` (never release) and fetch the shard
        itself, so a wedged transfer can never deadlock the pass."""
        st = self._staged[name]
        with TraceAnnotation("prefetch.acquire", sub=name):
            t0 = time.perf_counter()
            staged = st.event.wait(timeout)
            exposed = time.perf_counter() - t0
        if not staged:
            raise DemandTimeout(
                f"{name} not staged within {timeout:.3f}s")
        if st.error is not None:
            raise st.error
        self.stats.copy_s_exposed += exposed
        self.stats.copy_s_hidden += max(st.copy_s - exposed, 0.0)
        return st.tree

    def release(self, name: str):
        """Free ``name``'s scratch slot (compute for it has been issued)."""
        st = self._staged.pop(name)
        st.tree = None
        with self._lock:
            self._drop_held(st)
        (self._demand_sem if st.pool == "demand" else self._sem).release()

    def discard(self, name: str):
        """Drop a FAILED entry whose error the consumer just consumed
        (DESIGN.md §15): frees its scratch slot iff a staging worker
        actually held one (copy-failure entries), never for a
        ``WorkerLost`` entry — the dead worker held no slot for it. The
        caller sync-fetches the shard itself; without this, a failed
        entry would pin its slot for the rest of the pass and a
        single-slot session would deadlock on the next acquire."""
        with self._lock:
            st = self._staged.pop(name)
            st.tree = None
            self._drop_held(st)
            if st.holds_slot:
                (self._demand_sem if st.pool == "demand"
                 else self._sem).release()

    def abandon(self, name: str):
        """Drop a timed-out entry from the session (DESIGN.md §15). If
        its copy already finished, the slot frees now; otherwise the
        worker frees it when the copy lands — either way exactly once,
        and the caller must not touch ``name`` again this pass."""
        with self._lock:
            st = self._staged.pop(name)
            st.abandoned = True
            self.stats.abandoned += 1
            if st.event.is_set():
                st.tree = None
                self._drop_held(st)
                (self._demand_sem if st.pool == "demand"
                 else self._sem).release()

    def finish(self):
        """End the session; joins the transfer threads."""
        if not self.active:
            return
        with self._demand_cv:
            self._closed = True
            self._demand_cv.notify()
        # unconsumed slots (error paths) must not deadlock the workers
        while self._staged:
            name = next(iter(self._staged))
            self._staged[name].event.wait()
            self.release(name)
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._demand_thread is not None:
            self._demand_thread.join()
            self._demand_thread = None
