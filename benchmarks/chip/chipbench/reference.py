"""Plain float32 reference of a dense SwiGLU decoder (Qwen2 / Llama
layout), written from the published architecture and independent of the
program: pre-norm RMSNorm, grouped-query attention with rotary positions
(the half-split rotation), optional q/k/v biases, a SwiGLU MLP, a final
RMSNorm and a tied or separate output head. Every matmul runs at
``Precision.HIGHEST``, so the chip does not round it to bfloat16.

It runs layer by layer over a block of sequences, so only one layer's
weights and the block's residuals are on the device at a time.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

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


def _layer(w, x, *, heads, kv_heads, head_dim, theta, eps, low):
    """One decoder layer over a block of sequences x: (N, S, d)."""
    import jax
    import jax.numpy as jnp
    mm = _low_mm(low)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a = w["attn"]
    S = x.shape[1]
    pos = jnp.arange(S)

    def one(xs):
        h = _rmsnorm(xs, w["ln1"], eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(S, heads, head_dim), pos, theta)
        k = _rope(k.reshape(S, kv_heads, head_dim), pos, theta)
        v = v.reshape(S, kv_heads, head_dim)
        xs = xs + mm(_attention(q, k, v), a["wo"])
        h = _rmsnorm(xs, w["ln2"], eps)
        f = w["ffn"]
        g = mm(h, f["w_gate"])
        return xs + mm(g * jax.nn.sigmoid(g) * mm(h, f["w_up"]),
                       f["w_down"])

    return jax.lax.map(one, x)


def logits_at(raw: dict, params: dict, seqs, low: Optional[str] = None,
              length: int = 0, rows: int = 0) -> list:
    """The logits that chose each served token: for each ``(prompt,
    served)`` pair, a ``(len(served), vocab)`` float32 array whose row
    ``i`` is the output at position ``len(prompt) - 1 + i``, teacher-forced
    on the prompt and the served tokens. ``params`` are the host weights
    the program was given. ``low`` (``"int8"`` or ``"fp8"``) computes every
    weight matmul with operands in that precision instead (the control). Sequences run one at a time,
    padded to ``length`` positions and ``rows`` served tokens (at least
    their longest), so a cell compiles the same programs every run."""
    import jax
    import jax.numpy as jnp
    S = max([length] + [len(p) + len(s) - 1 for p, s in seqs])
    R = max([rows] + [len(s) for _, s in seqs])
    embed = jnp.asarray(params["embed"])
    xs = []
    for p, s in seqs:
        row = np.zeros((1, S), np.int32)
        n = len(p) + len(s) - 1
        row[0, :n] = np.concatenate([np.asarray(p, np.int32),
                                     np.asarray(s[:-1], np.int32)])
        xs.append(jnp.take(embed, jnp.asarray(row), axis=0)
                  .astype(jnp.float32))
    layer = jax.jit(partial(
        _layer, heads=raw["num_attention_heads"],
        kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
        theta=float(raw["rope_theta"]), eps=float(raw["rms_norm_eps"]),
        low=low))
    for i in range(raw["num_hidden_layers"]):
        w = jax.tree.map(lambda a: jnp.asarray(a[i]), params["layers"])
        xs = [layer(w, x) for x in xs]
        del w
    head = embed.T if raw["tie_word_embeddings"] else \
        jnp.asarray(params["unembed"])
    final = jnp.asarray(params["final_norm"])
    eps = float(raw["rms_norm_eps"])

    @jax.jit
    def out(x, start, final, head):
        xr = jax.lax.dynamic_slice_in_dim(
            jnp.pad(x[0], ((0, R), (0, 0))), start, R)
        return _low_mm(low)(
            _rmsnorm(xr, final.astype(jnp.float32), eps),
            head.astype(jnp.float32))

    return [np.asarray(out(x, len(p) - 1, final, head))[:len(s)]
            for x, (p, s) in zip(xs, seqs)]


def gaps(ref_logits, picked) -> np.ndarray:
    """How far below the reference's best logit each picked token's
    reference logit lies, row by row."""
    ref = np.asarray(ref_logits)
    picked = np.asarray(picked)
    return ref.max(-1) - ref[np.arange(len(picked)), picked]
