"""Seconds from process start to the window: weights from the seed, plan,
executor, warm-up of every shape and the loop's ramp."""


def read(w):
    return w.setup_s
