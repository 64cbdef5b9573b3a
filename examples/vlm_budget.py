"""VLMOpt demo: high-resolution vision encoding under a VRAM budget.

Shows (a) the runnable flash/Q-chunked vision encoder matching the
full-attention reference, (b) the analytic VRAM-demand grid reproducing
the paper's OOM pattern and ~10x reduction for CR1-class models, and
(c) the language-side tier plan for the paper's VLM arch under client
budgets via a planning-only `repro.Session`.

    PYTHONPATH=src python examples/vlm_budget.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import Session
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import CLI1, InferenceSetting, run_install
from repro.core.vlmopt import (RESOLUTIONS, VisionConfig, init_vision_params,
                               n_vision_tokens, vision_encode, vlm_peak_vram)


def main():
    enable_compile_cache()
    # runnable: small encoder, flash vs reference numerics
    vc_small = VisionConfig(d=64, layers=2, heads=4)
    params = init_vision_params(jax.random.PRNGKey(0), vc_small, jnp.float32)
    patches = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64))
    ref = vision_encode(params, vc_small, patches, flash=False)
    for qc in (32, 64, 128):
        out = vision_encode(params, vc_small, patches, flash=True, q_chunk=qc)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"flash q_chunk={qc:4d}: max err vs full attention {err:.2e}")

    # analytic: CR1-class demand grid (paper Tables 7-8 shape)
    vc = VisionConfig()
    print("\nVRAM feasibility (baseline -> VLMOpt), CR1-class encoder:")
    print(f"{'res':>7s} {'tokens':>7s} " + " ".join(f"{b:>7}" for b in
          ("2G", "8G", "14.5G", "20G")))
    for res in RESOLUTIONS:
        row = []
        for bg in (2e9, 8e9, 14.5e9, 20e9):
            base = vlm_peak_vram(vc, res, int(6e9), vlmopt=False) <= bg
            opt = vlm_peak_vram(vc, res, int(1.2e9), vlmopt=True) <= bg
            row.append(f"{'ok' if base else 'OOM'}->{'ok' if opt else 'OOM'}")
        print(f"{res:>7s} {n_vision_tokens(vc, res):7d} "
              + " ".join(f"{r:>7s}" for r in row))
    red = 20e9 / vlm_peak_vram(vc, "1440p", int(1.2e9), vlmopt=True)
    print(f"\n1440p peak-VRAM reduction vs the paper's 20G vLLM baseline: "
          f"{red:.1f}x")

    # language side: plan the paper's VLM arch under laptop-class budgets
    # (planning-only Session — vlm executes through the encoder above;
    # the tier table covers the decode-phase language stack)
    full = get_config("qwen2-vl-7b")
    db = run_install(CLI1, quick=True)  # one install profile for both plans
    print(f"\n{full.name} language-stack tier plan on {CLI1.name}:")
    for gb in (4.0, 8.0):
        sess = Session.open(full, CLI1, int(gb * 1e9),
                            InferenceSetting(batch=1, context=4096), db=db)
        est = sess.estimates(4096)
        print(f"  {gb:4.1f}G: pinned {est['pinned_bytes']/1e9:5.2f}G "
              f"est TTFT(4k) {est['ttft_s']:6.2f}s "
              f"est TPS {est['tps']:6.1f}")


if __name__ == "__main__":
    main()
