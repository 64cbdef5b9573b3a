"""Share of the traced window in which no operation ran on the chip: one
minus the union of the ``XLA Ops`` intervals over the window, in %."""


def read(w):
    if w.trace is None or w.trace.chips == 0:
        return None
    return 100.0 * w.trace.idle_share
