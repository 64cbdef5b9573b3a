"""Share of the roofline reached by the ``_attn_decode_step``
executables: the least time the window's decode attention needs over
their device time in the trace, in %. The least time is taken per step
that decoded, in each layer: the larger of the operations over the bf16
peak and the bytes (the layer's attention weights once, each decoded
token's valid cache positions) over the HBM peak."""
from chipbench.flops import attn_decode_bytes, attn_decode_flops, roofline_s


def read(w):
    if w.trace is None or w.peaks is None:
        return None
    device_s = w.trace.module_s.get("_attn_decode_step", 0.0)
    if device_s <= 0:
        return None
    s, p = w.shapes, w.peaks
    need = sum(s.layers * roofline_s(attn_decode_flops(s, c),
                                     attn_decode_bytes(s, c),
                                     p.bf16_flops, p.hbm_bytes)
               for _, c in w.step_tokens() if c)
    return 100.0 * need / device_s if need else None
