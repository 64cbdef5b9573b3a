"""The roofline: the least time the chip could take for work whose
operations and bytes a family counts (``families/<family>.py``), and a
kernel's share of it over a traced window."""
from __future__ import annotations

BF16 = 2


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes: float) -> float:
    """Least time the chip could take for one call."""
    return max(flops / peak_flops, nbytes / peak_bytes)


def kernel_roofline(w, kernel: str):
    """Share of the roofline reached by the ``kernel`` executables in a
    traced window, in %: the least time the window's work in them needs,
    summed over its steps from the family's ``KERNELS[kernel]`` counts,
    over their device time in the trace. ``None`` where the window has no
    trace or peaks, the family counts nothing for the kernel, or the
    kernel never ran."""
    if w.trace is None or w.peaks is None or w.family is None:
        return None
    work = w.family.KERNELS.get(kernel)
    device_s = w.trace.module_s.get(kernel, 0.0)
    if work is None or device_s <= 0:
        return None
    p = w.peaks
    need = sum(calls * roofline_s(flops, nbytes, p.bf16_flops, p.hbm_bytes)
               for prompts, decoded in w.step_tokens()
               for calls, flops, nbytes in work(w.shapes, prompts, decoded))
    return 100.0 * need / device_s if need else None
