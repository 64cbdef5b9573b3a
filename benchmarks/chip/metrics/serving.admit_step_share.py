"""Share of the window spent in ``step()`` calls that admitted a request
(its first token, from the prefill, came out of that step), by the
harness's clock around each step."""


def read(w):
    admit = sum(s.t1 - s.t0 for s in w.steps if s.first_tokens or s.errors)
    return 100.0 * admit / w.seconds
