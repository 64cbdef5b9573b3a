"""Share of the roofline reached by the ``_ffn_step`` executables: the
least time the window's FFN work needs over their device time in the
trace, in %. The least time is taken per step, over every row the step
ran through the FFN (each prompt it prefilled and each token it decoded):
in each layer, the larger of the rows' operations over the bf16 peak and
the layer's weights once plus the rows' residuals over the HBM peak. Any
implementation of the step needs at least that."""
from chipbench.flops import ffn_bytes, ffn_flops, roofline_s


def read(w):
    if w.trace is None or w.peaks is None:
        return None
    device_s = w.trace.module_s.get("_ffn_step", 0.0)
    if device_s <= 0:
        return None
    s, p = w.shapes, w.peaks
    rows = [sum(prompts) + len(decoded) for prompts, decoded in
            w.step_tokens()]
    need = sum(s.layers * roofline_s(ffn_flops(s, n), ffn_bytes(s, n),
                                     p.bf16_flops, p.hbm_bytes)
               for n in rows if n)
    return 100.0 * need / device_s if need else None
