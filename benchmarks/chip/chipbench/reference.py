"""Plain float32 arithmetic that the families' references are written
with, independent of the program: matmuls at ``Precision.HIGHEST`` (so
the chip does not round them to bfloat16), RMSNorm, rotary positions,
causal grouped-query attention, the low-precision roundings of the
controls, and the gap of a picked token below the reference's best.
Each family's ``logits_at`` (``families/<family>.py``) builds on them.
"""
from __future__ import annotations

import numpy as np


def _mm(a, b):
    import jax.numpy as jnp
    from jax import lax
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, positions, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA for one sequence. q: (S, H, hd); k, v: (S, KV, hd)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    S, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(S, KV, H // KV, hd)
    s = jnp.einsum("tkgd,skd->kgts", qg, k,
                   precision=lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=lax.Precision.HIGHEST)
    return o.reshape(S, H * hd)


def _q8(x, axis):
    """Symmetric int8 round trip with one scale per slice along ``axis``
    (the control's arithmetic: int8 operands, wide accumulation)."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _qf8(x, axis):
    """float8 (e4m3) round trip with one scale per slice along ``axis``,
    the largest magnitude at the format's largest finite value."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


LOW = {"int8": _q8, "fp8": _qf8}


def _low_mm(kind):
    """Low-precision activations (a scale per token) times low-precision
    weights (a scale per output column); ``None`` is the plain matmul."""
    if kind is None:
        return _mm
    q = LOW[kind]
    return lambda a, w: _mm(q(a, -1), q(w, 0))


def gaps(ref_logits, picked) -> np.ndarray:
    """How far below the reference's best logit each picked token's
    reference logit lies, row by row."""
    ref = np.asarray(ref_logits)
    picked = np.asarray(picked)
    return ref.max(-1) - ref[np.arange(len(picked)), picked]
