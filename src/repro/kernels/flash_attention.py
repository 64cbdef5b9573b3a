"""Pallas TPU flash attention with a Q-chunk knob (VLMOpt's Q-chunking).

Grid: (batch*q_heads, T_q/block_q, T_k/block_k); the kv axis is the
innermost ("arbitrary") dimension so the online-softmax state lives in VMEM
scratch across kv steps. GQA is handled in the index maps (kv head =
q_head // group) — repeated KV heads are never materialised.

block_q is exactly the paper's Q-chunk: shrinking it bounds the VMEM
working set for arbitrarily long vision/text sequences at some throughput
cost (measured in the benchmarks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q, block_k, n_k, causal, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip fully-masked kv blocks
    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, hd)
        k = k_ref[0].astype(jnp.float32)          # (block_k, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[:, None]), 0.0)
        l_new = l_prev * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128,
                    interpret=False):
    """q: (B, H, Tq, hd); k, v: (B, KV, Tk, hd) with H % KV == 0.

    Returns (B, H, Tq, hd).
    """
    B, H, Tq, hd = q.shape
    KV, Tk = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    assert Tq % block_q == 0 and Tk % block_k == 0
    n_q, n_k = Tq // block_q, Tk // block_k
    scale = hd ** -0.5

    qf = q.reshape(B * H, Tq, hd)
    kf = k.reshape(B * KV, Tk, hd)
    vf = v.reshape(B * KV, Tk, hd)

    def q_map(bh, i, j):  # noqa: ARG001
        return (bh, i, 0)

    def kv_map(bh, i, j):  # noqa: ARG001
        b, h = bh // H, bh % H
        return (b * KV + h // G, j, 0)

    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, n_k=n_k, causal=causal,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Tq, hd)
