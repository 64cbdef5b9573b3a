"""Streamed (pipelined copy-compute) matmul — the paper's core mechanism,
expressed at the TPU memory hierarchy.

The paper overlaps PCIe weight copies with GPU compute through a VRAM
scratch double-buffer. The TPU-native analogue one level down: weight tiles
stream HBM->VMEM while the MXU computes the previous tile. Pallas emits
exactly this double-buffered DMA pipeline from the BlockSpecs: the kv grid
axis is "arbitrary" (sequential), so tile j+1's DMA overlaps tile j's dot.

Also provides the quantised variants: weights stream in int8 (per-group
symmetric scales) or packed int4 (two nibbles per byte, per-group
asymmetric scale + zero-point, DESIGN.md §11) and dequantise in VMEM —
halving / quartering the streamed bytes, which is how the paper's q4/q2
GGUF models keep the slow tier affordable.

Grouping convention shared by every quantiser here: for a (K, N) matrix and
a nominal group size ``g0``, the K axis is split into ``G = ceil(K / g0)``
*balanced* groups of ``g = ceil(K / G)`` rows (edge-padded up to ``G * g``
before quantisation; padding replicates the last row so group min/max and
abs-max are unchanged, then the quantised rows are sliced back to K). The
invariant ``g == ceil(K / G)`` lets every consumer recover the group size
from array shapes alone — no side-channel metadata. When ``g0`` divides K
this degenerates to the original exact-tiling behaviour bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Nominal quantisation group size along K (AWQ-style); balanced groups of
# ceil(K / ceil(K / GROUP_SIZE)) rows are derived from it per matrix.
GROUP_SIZE = 128


def _balanced_groups(K, g0):
    """(G, g): G balanced groups of g rows covering K (g*G >= K, g <= g0)."""
    G = -(-K // g0)
    return G, -(-K // G)


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_quant_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = s_ref[0, 0].astype(jnp.float32)  # (block_n,)
    w = w_ref[...].astype(jnp.float32) * s[None, :]
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def streamed_matmul(x, w, *, block_m=128, block_n=128, block_k=512,
                    interpret=False):
    """x: (M, K) resident activations; w: (K, N) streamed weight tiles."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    n_k = K // block_k
    kernel = functools.partial(_mm_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)


def quantize_int8(w, block_k=512):
    """Per-(k-group, column) symmetric int8 quantisation.

    Ragged K is supported: groups are balanced (``ceil(K / G)`` rows each,
    see module docstring) instead of dying on the seed's hard
    ``K % block_k == 0`` assert. Divisible K is bit-identical to before.
    Returns ``(q (K, N) int8, scales (G, 1, N) fp32)``.
    """
    K, N = w.shape
    G, g = _balanced_groups(K, block_k)
    wf = w.astype(jnp.float32)
    if G * g != K:
        wf = jnp.pad(wf, ((0, G * g - K), (0, 0)), mode="edge")
    wt = wf.reshape(G, g, N)
    scale = jnp.max(jnp.abs(wt), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(wt / scale), -127, 127).astype(jnp.int8)
    return q.reshape(G * g, N)[:K], scale.astype(jnp.float32)


def quantize_int4(w, group_size=GROUP_SIZE):
    """AWQ-style asymmetric int4 grouped quantisation with nibble packing.

    Per balanced k-group and output column: ``scale = (max - min) / 15``
    (fp16), ``zero = round(-min / scale)`` in [0, 15] (uint8), codes
    ``q = round(w / scale) + zero`` in [0, 15]. Two consecutive K rows pack
    into one byte, low nibble = even row. Returns
    ``(packed (K//2, N) uint8, scales (G, N) fp16, zeros (G, N) uint8)``.
    """
    K, N = w.shape
    if K % 2:
        raise ValueError(
            f"int4 nibble packing needs an even reduction dim, got K={K}")
    G, g = _balanced_groups(K, group_size)
    wf = w.astype(jnp.float32)
    if G * g != K:
        wf = jnp.pad(wf, ((0, G * g - K), (0, 0)), mode="edge")
    wt = wf.reshape(G, g, N)
    wmin = jnp.min(wt, axis=1)                      # (G, N)
    wmax = jnp.max(wt, axis=1)
    scale = jnp.maximum((wmax - wmin) / 15.0, 1e-8)
    zero = jnp.clip(jnp.round(-wmin / scale), 0.0, 15.0)
    q = jnp.clip(jnp.round(wt / scale[:, None, :]) + zero[:, None, :], 0, 15)
    q = q.reshape(G * g, N)[:K].astype(jnp.uint8)
    packed = q[0::2] | (q[1::2] << 4)
    return packed, scale.astype(jnp.float16), zero.astype(jnp.uint8)


def dequant_int8(w_q, scales):
    """Inverse of :func:`quantize_int8`; fp32 result. Accepts leading batch
    dims (stacked experts): ``w_q (..., K, N)``, ``scales (..., G, 1, N)``."""
    K, N = w_q.shape[-2:]
    lead = w_q.shape[:-2]
    G = scales.shape[-3]
    g = -(-K // G)
    wf = w_q.astype(jnp.float32)
    if G * g != K:
        wf = jnp.pad(wf, [(0, 0)] * len(lead) + [(0, G * g - K), (0, 0)])
    w = wf.reshape(lead + (G, g, N)) * scales.astype(jnp.float32)
    return w.reshape(lead + (G * g, N))[..., :K, :]


def unpack_int4(packed):
    """(..., K//2, N) packed bytes -> (..., K, N) uint8 codes in [0, 15]."""
    lead = packed.shape[:-2]
    Kh, N = packed.shape[-2:]
    lo = packed & 0xF
    hi = packed >> 4
    return jnp.stack([lo, hi], axis=-2).reshape(lead + (2 * Kh, N))


def dequant_int4(packed, scales, zeros):
    """Inverse of :func:`quantize_int4`; fp32 result. Accepts leading batch
    dims: ``packed (..., K//2, N)``, ``scales``/``zeros (..., G, N)``."""
    lead = packed.shape[:-2]
    K, N = 2 * packed.shape[-2], packed.shape[-1]
    G = scales.shape[-2]
    g = -(-K // G)
    q = unpack_int4(packed).astype(jnp.float32)
    if G * g != K:
        q = jnp.pad(q, [(0, 0)] * len(lead) + [(0, G * g - K), (0, 0)])
    qt = q.reshape(lead + (G, g, N))
    s = scales.astype(jnp.float32)[..., :, None, :]
    z = zeros.astype(jnp.float32)[..., :, None, :]
    return ((qt - z) * s).reshape(lead + (G * g, N))[..., :K, :]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def streamed_matmul_int8(x, w_q, scales, *, block_m=128, block_n=128,
                         block_k=512, interpret=False):
    """x: (M, K); w_q: (K, N) int8; scales: (K/block_k, 1, N)."""
    M, K = x.shape
    _, N = w_q.shape
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    assert scales.shape[0] == K // block_k
    n_k = K // block_k
    kernel = functools.partial(_mm_quant_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1, block_n), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_q, scales)


def _mm_int4_kernel(x_ref, w_ref, s_ref, z_ref, o_ref, acc_ref, *, n_k):
    """k-loop body with int4 dequant fused in: the packed bytes arrive in
    VMEM via the same double-buffered DMA as fp16 tiles; unpack, shift by
    the zero-point and scale all happen in-register before the MXU dot, so
    no fp16 weight tile is ever materialised outside VMEM (DESIGN.md §11).

    Mosaic has no uint8 -> float conversion and no uint8 shift, so the
    packed bytes widen to int32 before the nibble split; scales and
    zero-points arrive as float32 (the wrapper converts the small (G, N)
    arrays once per call)."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p32 = w_ref[...].astype(jnp.int32)       # (block_k // 2, block_n)
    half, bn = p32.shape
    bk = 2 * half
    lo = (p32 & 0xF).astype(jnp.float32)
    hi = ((p32 >> 4) & 0xF).astype(jnp.float32)
    q = jnp.stack([lo, hi], axis=1).reshape(bk, bn)
    gblk = s_ref.shape[0]                    # groups inside this k-block
    group = bk // gblk
    s = jnp.broadcast_to(s_ref[:, 0, :][:, None, :],
                         (gblk, group, bn)).reshape(bk, bn)
    z = jnp.broadcast_to(z_ref[:, 0, :][:, None, :],
                         (gblk, group, bn)).reshape(bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), (q - z) * s,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def streamed_matmul_int4(x, w_packed, scales, zeros, *, block_m=128,
                         block_n=128, block_k=None, interpret=False):
    """x: (M, K); w_packed: (K//2, N) uint8, two int4 codes per byte (low
    nibble = even K row); scales: (G, N) fp16; zeros: (G, N) uint8.

    ``block_k`` defaults to the quantisation group size (recovered from the
    scale shape) and must be a multiple of it, so each k-block holds whole
    groups and the in-kernel scale/zero broadcast is a static reshape.
    Scales and zero-points enter the kernel as float32 ``(G, 1, N)``, so
    their blocks ``(groups per k-block, 1, block_n)`` keep the last two
    dims TPU-tileable whatever the group count per block.
    """
    M, K = x.shape
    Kh, N = w_packed.shape
    assert K == 2 * Kh, (K, Kh)
    G = scales.shape[0]
    group = -(-K // G)
    if group * G != K:
        raise ValueError(
            f"K={K} is ragged over {G} groups — use dequant_int4 instead")
    if block_k is None:
        block_k = group
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    assert block_k % group == 0 and block_k % 2 == 0
    n_k = K // block_k
    gblk = block_k // group
    kernel = functools.partial(_mm_int4_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k // 2, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((gblk, 1, block_n), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((gblk, 1, block_n), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed, scales.astype(jnp.float32).reshape(G, 1, N),
      zeros.astype(jnp.float32).reshape(G, 1, N))
