"""95th percentile of the gaps between consecutive output tokens of the
same request, over every gap that ends in the window (host clock). A
prefill that stalls the decode batch lands here."""
from chipbench.stats import percentile


def read(w):
    gaps = [b - a for r in w.sent for a, b in zip(r.token_at, r.token_at[1:])
            if w.within(b)]
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
