"""Time the serving thread spent admitting requests (``serving.admit``:
the prefill and the first token's read-back), over the admissions that
started in the traced window, in ms."""
from chipbench import spans


def read(w):
    s = spans.for_window(w)
    if s is None or not s.counts.get(spans.ADMIT):
        return None
    return 1e3 * s.admit_s / s.counts[spans.ADMIT]
