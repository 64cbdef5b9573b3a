"""Share of the roofline reached by the ``_ffn_step`` executables: the
least time the window's FFN work needs over their device time in the
trace, in %. The least time is taken per step, over every row the step
ran through the FFN (each prompt it prefilled and each token it decoded):
in each layer, the larger of the rows' operations over the bf16 peak and
the layer's weights once plus the rows' residuals over the HBM peak. Any
implementation of the step needs at least that. The family counts the
work (``KERNELS["_ffn_step"]``)."""
from chipbench.flops import kernel_roofline


def read(w):
    return kernel_roofline(w, "_ffn_step")
