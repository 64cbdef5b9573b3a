"""Closed-loop traffic from a data file and a seed.

A mix file gives the number of clients, the HBM budget as a multiple of
the configuration's weight bytes, and the length distributions. Prompt
lengths sit at ``levels`` quantiles of a lognormal clipped to
``[min, max]``; output lengths are uniform integers in ``[min, max]``.
The multiset of (prompt, output) sizes is the same for every seed (it is
drawn from a fixed generator), so runs on different seeds do the same
work; the seed only orders the requests and draws their token ids.
"""
from __future__ import annotations

import json
import statistics
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

SIZES_SEED = 0x5EED


@dataclass(frozen=True)
class Spec:
    path: str
    clients: int
    hbm_budget_x: float
    prompt_levels: tuple
    output_min: int
    output_max: int
    requests: int
    ramp_requests: int

    @property
    def max_seq(self) -> int:
        """Cache length the session is opened with: the longest prompt
        plus the longest output."""
        return max(self.prompt_levels) + self.output_max


@dataclass
class RequestSpec:
    index: int
    prompt: np.ndarray     # (T,) int32
    max_new_tokens: int


def prompt_levels(median: float, sigma: float, lo: int, hi: int,
                  levels: int) -> tuple:
    """Lengths at the lognormal's ``(i + 0.5) / levels`` quantiles."""
    nd = statistics.NormalDist()
    out = []
    for i in range(levels):
        z = nd.inv_cdf((i + 0.5) / levels)
        out.append(int(min(hi, max(lo, round(median * np.exp(sigma * z))))))
    return tuple(out)


def load(path) -> Spec:
    raw = json.loads(Path(path).read_text())
    if raw.get("loop") != "closed":
        raise ValueError(f"{path}: only closed-loop mixes are generated")
    p, o = raw["prompt"], raw["output"]
    return Spec(path=str(path), clients=int(raw["clients"]),
                hbm_budget_x=float(raw["hbm_budget_x"]),
                prompt_levels=prompt_levels(p["median"], p["sigma"],
                                            p["min"], p["max"],
                                            p["levels"]),
                output_min=int(o["min"]), output_max=int(o["max"]),
                requests=int(raw["requests"]),
                ramp_requests=int(raw["ramp_requests"]))


def sizes(spec: Spec) -> List[tuple]:
    """The seed-independent multiset of (prompt_len, max_new_tokens)."""
    rng = np.random.default_rng(SIZES_SEED)
    outs = rng.integers(spec.output_min, spec.output_max + 1,
                        size=spec.requests)
    levels = spec.prompt_levels
    return [(levels[i % len(levels)], int(outs[i]))
            for i in range(spec.requests)]


def generate(spec: Spec, vocab: int, seed: int) -> List[RequestSpec]:
    """The run's requests in the order clients send them."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(spec.requests)
    table = sizes(spec)
    out = []
    for i, j in enumerate(order):
        T, n = table[j]
        out.append(RequestSpec(i, rng.integers(0, vocab, size=T,
                                               dtype=np.int32), n))
    return out


def warmup(spec: Spec, vocab: int) -> List[RequestSpec]:
    """One short request per prompt length, at least one per client slot:
    the shapes the window will use, compiled before it opens."""
    rng = np.random.default_rng(SIZES_SEED)
    lens = list(spec.prompt_levels)
    while len(lens) < spec.clients:
        lens.append(min(spec.prompt_levels))
    return [RequestSpec(-1 - i, rng.integers(0, vocab, size=T,
                                             dtype=np.int32), 2)
            for i, T in enumerate(lens)]
