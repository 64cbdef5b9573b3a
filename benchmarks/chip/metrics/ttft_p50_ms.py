"""Median time to first token: from when a client sent a request to when
``step()`` handed over its first token, over every request whose first
token arrived in the window (host clock)."""
from chipbench.stats import percentile


def read(w):
    ttfts = [r.token_at[0] - r.sent_at for r in w.sent
             if r.token_at and w.within(r.token_at[0])]
    p = percentile(ttfts, 50)
    return None if p is None else p * 1e3
