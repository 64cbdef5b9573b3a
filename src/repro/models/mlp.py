"""FFN sub-layers: dense (swiglu/gelu) and capacity-based MoE.

MoE dispatch uses the GShard-style fixed-capacity scheme, but built with
scatter/gather (never a (T, E, C) one-hot einsum, which would not fit memory
at pod scale). Two execution paths:

- ``moe_ffn``: global-semantics, works on a single device (tests, smoke).
- ``moe_ffn_ep``: expert-parallel ``shard_map`` path — tokens replicated over
  the "model" axis, experts sharded over it; each model rank routes/dispatches
  locally for its expert slice and the partial outputs are psum-ed. This
  mirrors a TP all-reduce (no all-to-all needed) and is the default at scale.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.streamed_matmul import (GROUP_SIZE, dequant_int4,
                                           dequant_int8, quantize_int4,
                                           quantize_int8)
from repro.models.common import dense_init


# ----------------------------------------------------- weight quantisation
def quantize_weight_tree(p, weight_quant):
    """Quantise every ``w_*`` matrix in a param dict at install time
    (DESIGN.md §11). 2-D weights quantise directly; stacked (E, K, N)
    expert weights quantise per expert via vmap. Adds ``s_*`` scales (and
    ``z_*`` zero-points for int4) next to each quantised ``w_*``."""
    if weight_quant == "fp16":
        return p
    out = dict(p)
    for k in list(p):
        if not k.startswith("w_"):
            continue
        w = p[k]
        fn = {"int8": partial(quantize_int8, block_k=GROUP_SIZE),
              "int4": quantize_int4}[weight_quant]
        for _ in range(w.ndim - 2):
            fn = jax.vmap(fn)
        qs = fn(w)
        if weight_quant == "int8":
            out[k], out[f"s_{k[2:]}"] = qs
        else:
            out[k], out[f"s_{k[2:]}"], out[f"z_{k[2:]}"] = qs
    return out


# ---------------------------------------------------------------- dense ffn
def init_ffn_params(key, cfg, dtype, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        p = {
            "w_gate": dense_init(ks[0], (d, f), 0, dtype),
            "w_up": dense_init(ks[1], (d, f), 0, dtype),
            "w_down": dense_init(ks[2], (f, d), 0, dtype),
        }
    else:
        p = {
            "w_up": dense_init(ks[0], (d, f), 0, dtype),
            "w_down": dense_init(ks[1], (f, d), 0, dtype),
        }
    return quantize_weight_tree(p, cfg.weight_quant)


def ffn(params, cfg, x, policy):
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(x @ _dequant(params, "w_gate", x.dtype)) \
            * (x @ _dequant(params, "w_up", x.dtype))
    else:
        h = jax.nn.gelu(x @ _dequant(params, "w_up", x.dtype))
    h = policy.constrain(h, "ffn_hidden")
    return h @ _dequant(params, "w_down", x.dtype)


# ---------------------------------------------------------------- moe
def init_moe_params(key, cfg, dtype):
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_expert, m.n_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, E), 0, jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, f), 1, dtype),
        "w_up": dense_init(ks[2], (E, d, f), 1, dtype),
        "w_down": dense_init(ks[3], (E, f, d), 1, dtype),
    }
    if cfg.expert_quant == "int8":
        for k in ("w_gate", "w_up", "w_down"):
            w = p[k].astype(jnp.float32)
            scale = jnp.max(jnp.abs(w), axis=(1, 2), keepdims=True) / 127.0
            scale = jnp.maximum(scale, 1e-8)
            p[k] = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
            p[f"s_{k[2:]}"] = scale  # (E, 1, 1) fp32
    return quantize_weight_tree(p, cfg.weight_quant)


def _dequant(params, name, compute_dtype=jnp.bfloat16):
    w = params[name]
    if w.dtype == jnp.uint8:  # packed int4 + per-group scale/zero
        return dequant_int4(w, params[f"s_{name[2:]}"],
                            params[f"z_{name[2:]}"]).astype(compute_dtype)
    if w.dtype == jnp.int8:
        s = params[f"s_{name[2:]}"]
        if s.ndim == w.ndim + 1:  # grouped along K (weight_quant="int8")
            return dequant_int8(w, s).astype(compute_dtype)
        return (w.astype(jnp.float32) * s).astype(compute_dtype)
    return w


def _route(x, router, m):
    """x: (T, d) -> (gates (T,k), experts (T,k)). Router math in fp32."""
    logits = x.astype(jnp.float32) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _dispatch_positions(idx, n_local, keep_mask):
    """Position of each (token, choice) in its expert's capacity buffer.

    idx: (A,) local expert id per assignment; keep_mask: (A,) bool.
    Returns (A,) int positions (cumulative count per expert, scatter-ready).
    """
    onehot = jax.nn.one_hot(idx, n_local, dtype=jnp.int32) * keep_mask[:, None].astype(jnp.int32)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot  # exclusive
    return (pos_in_expert * onehot).sum(-1)


def _expert_compute(disp, params, cfg, expert_slice=None):
    """disp: (E_loc, C, d) -> (E_loc, C, d) via per-expert swiglu."""
    wg = _dequant(params, "w_gate", disp.dtype)
    wu = _dequant(params, "w_up", disp.dtype)
    wd = _dequant(params, "w_down", disp.dtype)
    if expert_slice is not None:
        wg, wu, wd = (jax.lax.dynamic_slice_in_dim(w, expert_slice[0], expert_slice[1], 0)
                      for w in (wg, wu, wd))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, wg)) * jnp.einsum(
        "ecd,edf->ecf", disp, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_dispatch(x, gates, idx, m, n_local, local_offset, capacity):
    """Masked-capacity dispatch: scatter each kept (token, choice) into its
    expert's capacity buffer. Returns ``(disp, aux)`` where ``disp`` is the
    (n_local, capacity, d) expert input buffer and ``aux`` the scatter
    coordinates ``(safe_idx, safe_pos, keep, flat_gate, token_of)`` that
    ``moe_combine`` gathers back through. Shared verbatim by the monolithic
    ``moe_ffn`` path and the expert-granular engine phases (DESIGN.md §9),
    so both run the exact same capacity math."""
    T, d = x.shape
    A = T * m.top_k
    flat_idx = idx.reshape(A) - local_offset          # local expert ids
    flat_gate = gates.reshape(A)
    token_of = jnp.repeat(jnp.arange(T), m.top_k)
    local = (flat_idx >= 0) & (flat_idx < n_local)
    safe_idx = jnp.where(local, flat_idx, 0)
    pos = _dispatch_positions(safe_idx, n_local, local)
    keep = local & (pos < capacity)
    safe_pos = jnp.where(keep, pos, capacity - 1)

    xa = x[token_of] * keep[:, None].astype(x.dtype)
    disp = jnp.zeros((n_local, capacity, d), x.dtype)
    disp = disp.at[safe_idx, safe_pos].add(xa, mode="drop")
    return disp, (safe_idx, safe_pos, keep, flat_gate, token_of)


def moe_combine(out_buf, aux, n_tokens, dtype):
    """Gather expert outputs back to token order, gate-weight and sum the
    top-k contributions per token. Inverse of ``moe_dispatch``."""
    safe_idx, safe_pos, keep, flat_gate, token_of = aux
    d = out_buf.shape[-1]
    gathered = out_buf[safe_idx, safe_pos]            # (A, d)
    gathered = gathered * (flat_gate * keep.astype(jnp.float32)).astype(dtype)[:, None]
    return jnp.zeros((n_tokens, d), dtype).at[token_of].add(gathered)


def _moe_local(x, params, cfg, n_local, local_offset, capacity, valid=None):
    """Core MoE over a local token set against experts [offset, offset+n_local).

    x: (T, d). Returns (T, d) partial output covering only local experts.
    ``valid`` (optional (T,) bool) masks padded tokens: they route to the
    out-of-range expert id E — never local on any rank — so they claim no
    capacity and contribute nothing to the combine (DESIGN.md §10).
    """
    m = cfg.moe
    T, d = x.shape
    gates, idx, _ = _route(x, params["router"], m)
    if valid is not None:
        idx = jnp.where(valid[:, None], idx, m.n_experts)
    disp, aux = moe_dispatch(x, gates, idx, m, n_local, local_offset,
                             capacity)
    # Slice expert weights only when they are still global-shaped (the EP
    # shard_map path already hands us local (E_loc, d, f) shards).
    slice_needed = params["w_gate"].shape[0] != n_local
    out_buf = _expert_compute(
        disp, params, cfg,
        expert_slice=(local_offset, n_local) if slice_needed else None)
    return moe_combine(out_buf, aux, T, x.dtype)


DROPLESS_MAX_ASSIGN = 4096


def capacity_is_dropless(n_tokens, m) -> bool:
    """True when ``capacity_of`` is in its dropless regime: capacity ==
    n_tokens bounds every expert's worst-case load, so no (token, choice)
    assignment can be dropped. Layer-major prefill may pad a tail chunk
    only here — padding grows the token count and thus the capacity, and
    in the truncating regime the padded run could keep assignments the
    unpadded chunk-major baseline drops (DESIGN.md §10)."""
    return n_tokens * m.top_k <= DROPLESS_MAX_ASSIGN


def capacity_of(n_tokens, m):
    """Expert capacity. Small token counts (decode iterations, smoke tests)
    get a *dropless* capacity so cached decode is exactly consistent with
    teacher-forced forward; large counts use the standard GShard
    capacity-factor truncation.

    Dropless bound: top-k indices are DISTINCT experts per token, so any
    single expert receives at most n_tokens assignments — the worst case is
    n_tokens, not n_tokens*top_k (a lossless 8x padding cut at decode for
    top-8 models; EXPERIMENTS.md §Perf iteration C1)."""
    if capacity_is_dropless(n_tokens, m):
        return n_tokens
    return max(1, int(n_tokens * m.top_k * m.capacity_factor / m.n_experts))


def moe_ffn(params, cfg, x, policy, valid=None):
    """Single-device / global-semantics MoE. x: (B, T, d).

    ``valid`` (optional (B, T) bool) marks real tokens: positions with
    ``False`` are routed to expert id E — out of dispatch range — so they
    claim no capacity slot and contribute zero output. Layer-major prefill
    uses this for its padded tail chunk (DESIGN.md §10); with ``valid``
    all-true the masking is the identity and the maths is bit-identical to
    the unmasked path.
    """
    m = cfg.moe
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    cap = capacity_of(B * T, m)
    out = _moe_local(xf, params, cfg, m.n_experts, 0, cap,
                     valid=None if valid is None else valid.reshape(B * T))
    return out.reshape(B, T, d)


def moe_ffn_ep(params, cfg, x, policy):
    """Expert-parallel MoE via shard_map over the policy's mesh.

    Tokens are replicated across "model" (they already are at the FFN input in
    our TP scheme); each model rank dispatches to its local expert slice and
    partial outputs are psum-ed over "model" — comms shape identical to a TP
    dense FFN (one all-reduce), no all-to-all required.
    """
    mesh = policy.mesh
    m = cfg.moe
    ep_axis = "model"
    ep = mesh.shape[ep_axis]
    n_local = m.n_experts // ep
    B, T, d = x.shape
    cap = capacity_of(B * T // policy.dp_size, m)

    batch_spec = policy.spec("resid")  # e.g. P(("pod","data"), None, None)
    wkeys = [k for k in params if k.startswith(("w_", "s_", "z_"))]
    # experts are stacked on axis 0 for every key; quantised trees carry
    # extra trailing dims (grouped scales are (E, G, 1, f)), so build each
    # spec from the array's own rank
    in_specs = (batch_spec, P()) + tuple(
        P(ep_axis, *([None] * (params[k].ndim - 1))) for k in wkeys)

    @partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
             out_specs=batch_spec)
    def _sharded(xl, router, *ws):
        rank = jax.lax.axis_index(ep_axis)
        p = {"router": router, **dict(zip(wkeys, ws))}
        Bl, Tl, _ = xl.shape
        out = _moe_local(xl.reshape(Bl * Tl, d), p, cfg, n_local,
                         rank * n_local, cap)
        out = jax.lax.psum(out, ep_axis)
        return out.reshape(Bl, Tl, d)

    return _sharded(x, params["router"], *(params[k] for k in wkeys))


def moe_block(params, cfg, x, policy):
    if policy.mesh is not None and cfg.moe.n_experts % policy.mesh.shape["model"] == 0:
        return moe_ffn_ep(params, cfg, x, policy)
    return moe_ffn(params, cfg, x, policy)
