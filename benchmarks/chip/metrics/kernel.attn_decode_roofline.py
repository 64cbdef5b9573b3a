"""Share of the roofline reached by the ``_attn_decode_step``
executables: the least time the window's decode attention needs over
their device time in the trace, in %. The least time is taken per step
that decoded, in each layer: the larger of the operations over the bf16
peak and the bytes (the layer's attention weights once, each decoded
token's valid cache positions) over the HBM peak. The family counts the
work (``KERNELS["_attn_decode_step"]``)."""
from chipbench.flops import kernel_roofline


def read(w):
    return kernel_roofline(w, "_attn_decode_step")
