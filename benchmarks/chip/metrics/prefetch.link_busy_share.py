"""Share of the traced window in which a host-to-HBM transfer was in
flight: the union of the program's ``link.copy`` spans, on any thread
(staging workers and at-use fetches alike), over the window, in %."""
from chipbench import spans


def read(w):
    s = spans.for_window(w)
    if s is None or not s.counts.get(spans.LINK):
        return None
    return 100.0 * s.link_busy_s / s.window_s
