"""Share of the traced window in which the chip idled while the serving
thread was inside ``serving.step`` but in no blocking fetch: host work
(dispatch, uploads, sampling, bookkeeping) the chip waited on, in %."""
from chipbench import spans


def read(w):
    s = spans.for_window(w)
    if s is None or not s.chips or not s.counts.get(spans.STEP):
        return None
    return 100.0 * s.host_idle_s / s.window_s
