"""Share of the traced window in which the chip idled while the serving
thread waited inside a blocking fetch: a streamed sub-layer not yet
staged (``prefetch.acquire``) or a CPU-engine sub-layer copied at use
(``executor.fetch_at_use``), in %."""
from chipbench import spans


def read(w):
    s = spans.for_window(w)
    if s is None or not s.chips or not any(s.counts.get(n)
                                           for n in spans.FETCH):
        return None
    return 100.0 * s.fetch_idle_s / s.window_s
