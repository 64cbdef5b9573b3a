"""Whole-model share of the chip's bf16 peak: the forward operations of
every prompt prefilled and every token decoded in the window, over the
window's seconds times the peak, in %. The family counts the operations
(``prompt_flops``, ``token_flops``)."""


def read(w):
    if w.peaks is None or w.family is None:
        return None
    f, s = w.family, w.shapes
    total = 0.0
    for r in w.sent:
        for i, t in enumerate(r.token_at):
            if not w.within(t):
                continue
            if i == 0:
                total += f.prompt_flops(s, r.prompt_len)
            else:
                total += f.token_flops(s, r.prompt_len + i)
    return 100.0 * total / (w.seconds * w.peaks.bf16_flops)
