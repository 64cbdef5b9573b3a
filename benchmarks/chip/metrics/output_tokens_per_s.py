"""Every output token emitted in the window over the window's seconds."""


def read(w):
    tokens = sum(s.first_tokens + s.decode_tokens for s in w.steps)
    return tokens / w.seconds
