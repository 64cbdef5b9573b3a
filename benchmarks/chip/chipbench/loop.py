"""Closed-loop clients over a ``ContinuousBatcher``.

Each client sends its next request the moment its previous one completes
(zero think time), as an app that waits for each reply does. The loop is
driven by ``batcher.step()``, the incremental surface the gateway's pump
drives, and stamps every token with the host clock when ``step()`` hands
it over. Since each client reacts only to completions, the sequence of
admissions and tokens depends on the seed alone, not on timing.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

STEP = "chipbench.step"
CLIENTS = "chipbench.clients"


@dataclass
class Sent:
    """One request as its client saw it."""
    rid: int
    client: int
    prompt_len: int
    max_new_tokens: int
    sent_at: float
    request: object = None               # the program's Request
    token_at: List[float] = field(default_factory=list)
    done_at: Optional[float] = None
    error: Optional[str] = None

    @property
    def served(self) -> list:
        return list(self.request.generated)


@dataclass
class StepRecord:
    t0: float
    t1: float
    first_tokens: int        # requests whose first token came this step
    decode_tokens: int       # tokens of requests already past their first
    errors: int


class ClosedLoop:
    def __init__(self, batcher, requests, clients: int, annotate=False,
                 clock=time.perf_counter):
        from repro.core.serving import Request
        self._Request = Request
        self.batcher = batcher
        self.requests = requests
        self.clients = clients
        self.clock = clock
        self.annotate = annotate
        self.sent: List[Sent] = []
        self.by_rid = {}
        self.steps: List[StepRecord] = []
        self.completed = 0
        self._next = 0

    def _send(self, client: int):
        spec = self.requests[self._next % len(self.requests)]
        rid = self._next
        self._next += 1
        now = self.clock()
        req = self._Request(rid=rid, prompt=spec.prompt,
                            max_new_tokens=spec.max_new_tokens,
                            submitted_at=now)
        rec = Sent(rid, client, len(spec.prompt), spec.max_new_tokens, now,
                   request=req)
        self.sent.append(rec)
        self.by_rid[rid] = rec
        self.batcher.submit([req])

    def start(self):
        for c in range(self.clients):
            self._send(c)

    def step(self):
        if self.annotate:
            from jax.profiler import TraceAnnotation
            with TraceAnnotation(STEP):
                t0 = self.clock()
                events = self.batcher.step()
                t1 = self.clock()
            with TraceAnnotation(CLIENTS):
                self._deliver(events, t0, t1)
        else:
            t0 = self.clock()
            events = self.batcher.step()
            t1 = self.clock()
            self._deliver(events, t0, t1)

    def _deliver(self, events, t0, t1):
        first = decode = errors = 0
        finished = []
        for ev in events:
            rec = self.by_rid[ev.rid]
            if ev.error is not None:
                rec.error = ev.error
                rec.done_at = t1
                errors += 1
                finished.append(rec)
                continue
            if ev.index == 0:
                first += 1
            else:
                decode += 1
            rec.token_at.append(t1)
            if ev.done:
                rec.done_at = t1
                self.completed += 1
                finished.append(rec)
        self.steps.append(StepRecord(t0, t1, first, decode, errors))
        for rec in finished:
            self._send(rec.client)

    def run_until_completed(self, n: int):
        while self.completed < n:
            self.step()

    def run_for(self, seconds: float) -> tuple:
        """Step until ``seconds`` have passed; returns the window's
        ``(start, end)``, where ``end`` is when its last step returned."""
        start = self.clock()
        while self.clock() - start < seconds:
            self.step()
        return start, self.steps[-1].t1
