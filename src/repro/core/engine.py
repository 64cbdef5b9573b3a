"""Jitted sub-layer engine: per-(kind, shape) compiled step functions.

The seed executor dispatched ``attention_block``/``ffn``/``moe_ffn`` eagerly
per sub-layer call, rebuilding host trees and re-tracing nothing-in-common
graphs every chunk and decode step. This engine compiles one step function
per sub-layer *kind*; ``jax.jit``'s executable cache then keys on the
(tier, batch) activation shapes, so every layer, chunk and decode step of a
given shape reuses one executable:

- the layer index, cache position and weights are *traced* arguments (the
  per-layer weight trees share shapes, so they hit the same executable);
- KV caches are stacked ``(n_layers, B, KV, S, hd)`` arrays read with
  ``dynamic_index_in_dim`` and written back with
  ``dynamic_update_index_in_dim`` — no per-layer Python lists, no host tree
  rebuilds inside the decode loop;
- chunked prefill uses ``attend_cached`` (cache-wide mask, shapes
  independent of position), decode (T==1) uses ``attend_decode``;
- layer-major prefill (DESIGN.md §10) runs the ``*_prefill_step``
  variants: chunk position AND valid length are traced scalars, so one
  executable serves every chunk of every prompt — the tail chunk is padded
  to the chunk size and its garbage positions are masked out of the KV
  cache and the MoE routing capacity.

``trace_counts`` increments only while tracing, so tests can assert that
decode steps stop re-tracing after the first step.

Streamed dense FFN sub-layers can route their matmuls through the Pallas
``streamed_matmul`` kernel (the HBM->VMEM double-buffered DMA pipeline that
mirrors the paper's PCIe->VRAM scratch double-buffer one level down). That
path is on by default on TPU backends when block shapes divide; elsewhere it
would run the kernel interpreter per matmul, so it must be opted into with
``REPRO_STREAMED_FFN=1`` (tests do, for numerics).
"""
from __future__ import annotations

import os
from collections import Counter

import jax
import jax.numpy as jnp

from repro.kernels.streamed_matmul import (streamed_matmul,
                                           streamed_matmul_int4,
                                           streamed_matmul_int8)
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models.common import NoPolicy, rmsnorm


def _blocks_divide(dim: int, block: int) -> bool:
    """streamed_matmul clamps each block to min(block, dim); the clamped
    block must then divide the dim exactly."""
    return dim % min(block, dim) == 0


class SubLayerEngine:
    """Compiled sub-layer step functions shared across layers/chunks/steps."""

    # steps whose KV arguments are donated on accelerators: the stacked
    # (L,B,KV,S,hd) caches of the attention steps (DESIGN.md §7, §10), the
    # slot-threaded prefill (§12), the paged steps' page pools (§12) and
    # the pool-level fold / rollback steps (§12, §14). A caller must never
    # read an argument it passed at these positions again.
    DONATED_ARGS = {
        "attn_step": (2, 3),
        "attn_prefill_step": (2, 3),
        "attn_decode_step": (2, 3),
        "attn_prefill_slot_step": (2, 3),
        "attn_decode_paged_step": (2, 3),
        "attn_prefill_paged_step": (2, 3),
        "fold_page_step": (0, 1),
        "rollback_step": (0, 1),
    }

    def __init__(self, cfg, policy=None, use_streamed_mm=None):
        self.cfg = cfg
        self.policy = policy or NoPolicy()
        self.trace_counts = Counter()
        if use_streamed_mm is None:
            use_streamed_mm = (jax.default_backend() == "tpu"
                               or os.environ.get("REPRO_STREAMED_FFN") == "1")
        self.use_streamed_mm = use_streamed_mm
        self._mm_interpret = jax.default_backend() != "tpu"
        # dense FFN calls by the path they took: "jnp" or the Pallas kernel
        # for the weight's storage format ("pallas_bf16/int8/int4")
        self.ffn_paths = Counter()
        # donate the KV stacks / page pools on accelerators so each cache
        # update is in place; CPU ignores donation (and would warn)
        donating = jax.default_backend() != "cpu"
        for name, argnums in self.DONATED_ARGS.items():
            setattr(self, name, jax.jit(getattr(self, "_" + name),
                                        donate_argnums=argnums if donating
                                        else ()))
        self._ffn_step_jit = jax.jit(self._ffn_step,
                                     static_argnames=("streamed",))
        self.moe_step = jax.jit(self._moe_step)
        self.moe_prefill_step = jax.jit(self._moe_prefill_step)
        self.moe_route_prefill_step = jax.jit(self._moe_route_prefill_step)
        # expert-granular MoE phases (DESIGN.md §9): route-first so the
        # executor learns the demanded expert set, then one expert-compute
        # executable shared by the pinned and the streamed phase
        self.moe_route_step = jax.jit(self._moe_route_step)
        self.moe_experts_step = jax.jit(self._moe_experts_step)
        self.moe_combine_step = jax.jit(self._moe_combine_step)
        self.fold_expert_step = jax.jit(self._fold_expert_step)
        self.embed_step = jax.jit(self._embed_step)
        self.head_step = jax.jit(self._head_step)

    # ------------------------------------------------------------ attn
    def _attn_step(self, w, x, kstack, vstack, layer, pos):
        """x: (B,T,d); kstack/vstack: (L,B,KV,S,hd); layer, pos: traced i32.

        Returns (x + attn(x), kstack', vstack') with this layer's cache
        updated in place in the stack.
        """
        self.trace_counts["attn"] += 1
        cfg = self.cfg
        B, T, _ = x.shape
        positions = (pos + jnp.arange(T)[None, :]) * jnp.ones((B, 1), jnp.int32)
        h = rmsnorm(x, w["ln1"], cfg.norm_eps)
        ck = jax.lax.dynamic_index_in_dim(kstack, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(vstack, layer, 0, keepdims=False)
        out, cache = attn_mod.attention_block(
            w["attn"], cfg, h, positions, self.policy,
            cache={"k": ck, "v": cv}, cache_pos=pos)
        kstack = jax.lax.dynamic_update_index_in_dim(kstack, cache["k"],
                                                     layer, 0)
        vstack = jax.lax.dynamic_update_index_in_dim(vstack, cache["v"],
                                                     layer, 0)
        return x + out, kstack, vstack

    def _attn_prefill_step(self, w, x, kstack, vstack, layer, pos, valid_len):
        """Layer-major prefill attention (DESIGN.md §10).

        Same math as ``_attn_step`` plus a masked cache write: the last
        chunk of a prompt is padded to the chunk size, and the padded
        positions must never land in KV (a later pass or decode step would
        read them). ``pos`` and ``valid_len`` are traced i32 scalars, so
        one executable serves every chunk — full or tail — of every prompt
        length. Causality inside ``attend_cached`` already keeps valid
        queries away from the padded keys (they sit at strictly later
        positions), so the mask only has to protect the cache itself.
        """
        self.trace_counts["attn_prefill"] += 1
        ck = jax.lax.dynamic_index_in_dim(kstack, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(vstack, layer, 0, keepdims=False)
        out, ck, cv = self._prefill_attn_math(w, x, ck, cv, pos, valid_len)
        kstack = jax.lax.dynamic_update_index_in_dim(kstack, ck, layer, 0)
        vstack = jax.lax.dynamic_update_index_in_dim(vstack, cv, layer, 0)
        return x + out, kstack, vstack

    def _prefill_attn_math(self, w, x, ck, cv, pos, valid_len):
        """The cache-slice-independent core of a prefill attention step —
        shared by the layer-indexed, the slot-threaded and (modulo the
        gather/scatter) the paged variants, so they stay bit-identical by
        construction. Returns (out, ck, cv)."""
        cfg = self.cfg
        B, T, _ = x.shape
        positions = (pos + jnp.arange(T)[None, :]) * jnp.ones((B, 1),
                                                              jnp.int32)
        h = rmsnorm(x, w["ln1"], cfg.norm_eps)
        q, k, v = attn_mod.qkv_project(w["attn"], cfg, h, positions)
        q = self.policy.constrain(q, "heads")
        ck_new, cv_new = attn_mod.cache_update(ck, cv, k, v, pos)
        S = ck.shape[2]
        keep = (jnp.arange(S) < pos + valid_len)[None, None, :, None]
        ck = jnp.where(keep, ck_new, ck)
        cv = jnp.where(keep, cv_new, cv)
        ck = self.policy.constrain(ck, "kv_cache")
        cv = self.policy.constrain(cv, "kv_cache")
        o = attn_mod.attend_cached(q, ck, cv, pos)
        o = self.policy.constrain(o, "heads")
        out = o.reshape(B, T, -1) @ w["attn"]["wo"]
        return out, ck, cv

    def _attn_prefill_slot_step(self, w, x, kstack, vstack, layer, slot,
                                pos, valid_len):
        """Slot-threaded layer-major prefill attention (DESIGN.md §12).

        x: (1, T, d) — ONE admitted sequence; ``slot`` is its row in the
        shared stacked cache, traced like ``layer`` so every slot of every
        admission hits one executable. The slot row is sliced and written
        back *inside* the donated jitted step, replacing the serving-side
        ``kv.at[:, slot:slot+1].set`` that materialised a full-cache copy
        per admission. The math is ``_prefill_attn_math`` verbatim, so the
        path is bit-identical to the batch-wide prefill step.
        """
        self.trace_counts["attn_prefill_slot"] += 1
        L, B, KV, S, hd = kstack.shape
        ck = jax.lax.dynamic_slice(kstack, (layer, slot, 0, 0, 0),
                                   (1, 1, KV, S, hd))[0]
        cv = jax.lax.dynamic_slice(vstack, (layer, slot, 0, 0, 0),
                                   (1, 1, KV, S, hd))[0]
        out, ck, cv = self._prefill_attn_math(w, x, ck, cv, pos, valid_len)
        kstack = jax.lax.dynamic_update_slice(kstack, ck[None],
                                              (layer, slot, 0, 0, 0))
        vstack = jax.lax.dynamic_update_slice(vstack, cv[None],
                                              (layer, slot, 0, 0, 0))
        return x + out, kstack, vstack

    def _attn_decode_step(self, w, x, kstack, vstack, layer, pos_vec, active):
        """Fused multi-slot decode attention (DESIGN.md §7).

        x: (B, 1, d) — one new token per slot; pos_vec: (B,) i32 per-slot
        cache position; active: (B,) bool. Every slot attends at its own
        position via the vectorised mask in ``attend_decode``; cache writes
        go through a per-slot ``dynamic_update_slice`` and are masked so
        inactive slots' caches stay untouched. One call serves the whole
        batch, so a streamed sub-layer's weights are fetched once per
        iteration regardless of how many slots are in flight.
        """
        self.trace_counts["attn_decode"] += 1
        cfg = self.cfg
        B = x.shape[0]
        h = rmsnorm(x, w["ln1"], cfg.norm_eps)
        ck = jax.lax.dynamic_index_in_dim(kstack, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(vstack, layer, 0, keepdims=False)
        q, k, v = attn_mod.qkv_project(w["attn"], cfg, h, pos_vec[:, None])
        q = self.policy.constrain(q, "heads")
        ck_new, cv_new = attn_mod.cache_update_batched(ck, cv, k, v, pos_vec)
        ck_new = self.policy.constrain(ck_new, "kv_cache")
        cv_new = self.policy.constrain(cv_new, "kv_cache")
        keep = active[:, None, None, None]
        ck = jnp.where(keep, ck_new, ck)
        cv = jnp.where(keep, cv_new, cv)
        o = attn_mod.attend_decode(q, ck, cv, pos_vec)
        o = self.policy.constrain(o, "heads")
        out = o.reshape(B, 1, -1) @ w["attn"]["wo"]
        kstack = jax.lax.dynamic_update_index_in_dim(kstack, ck, layer, 0)
        vstack = jax.lax.dynamic_update_index_in_dim(vstack, cv, layer, 0)
        return x + out, kstack, vstack

    def _rollback_step(self, kstack, vstack, zero_from, active):
        """Zero KV at positions >= ``zero_from[b]`` on active rows, every
        layer at once — the stacked rejected-suffix rollback (DESIGN.md
        §14). The stacked cache is zero-initialised and append-only, so
        "never written" IS "all zeros": the masked zero-write restores the
        cache byte-identical to a run that never verified the rejected
        drafts. Rows whose suffix was already clean rewrite zeros with
        zeros — the call is idempotent and safe to issue batch-wide."""
        self.trace_counts["kv_rollback"] += 1
        S = kstack.shape[3]
        clear = (jnp.arange(S)[None, :] >= zero_from[:, None]) & active[:, None]
        keep = ~clear[None, :, None, :, None]
        return jnp.where(keep, kstack, 0), jnp.where(keep, vstack, 0)

    # ------------------------------------------------------------ paged kv
    # The paged cache (DESIGN.md §12) stores KV in physical pages
    # (P, KV, page_size, hd); a per-layer table (B, n_blocks) maps each
    # slot's logical blocks to pages. Writes scatter through the table
    # (invalid/masked positions are routed to page 0, the null sink, so
    # no conditional is needed); reads gather ``pool[table]`` and reshape
    # to the exact (B, KV, S, hd) stacked view, after which the attention
    # math is shared with the stacked steps — garbage in unwritten page
    # slots sits at masked positions, whose softmax weight underflows to
    # exactly 0.0, keeping the paged paths bit-identical to stacked.
    @staticmethod
    def _pool_view(pool, table):
        """Gather (P, KV, ps, hd) pages into a (B, KV, n_blocks*ps, hd)
        stacked-cache view through the page table (B, n_blocks)."""
        B, nblk = table.shape
        g = jnp.transpose(pool[table], (0, 2, 1, 3, 4))
        return g.reshape(B, g.shape[1], nblk * pool.shape[2], g.shape[4])

    def _attn_decode_paged_step(self, w, x, k_pool, v_pool, table,
                                pos_vec, active):
        """Fused multi-slot decode against the page pool.

        x: (B, 1, d); table: (B, n_blocks) physical page ids of the
        CURRENT layer; pos_vec/active as in ``_attn_decode_step``. The new
        token's k/v scatter into page ``table[b, pos_b // ps]`` at offset
        ``pos_b % ps`` (inactive slots write the null page), then the
        gathered view feeds the same ``attend_decode``.
        """
        self.trace_counts["attn_decode_paged"] += 1
        cfg = self.cfg
        B = x.shape[0]
        ps = k_pool.shape[2]
        h = rmsnorm(x, w["ln1"], cfg.norm_eps)
        q, k, v = attn_mod.qkv_project(w["attn"], cfg, h, pos_vec[:, None])
        q = self.policy.constrain(q, "heads")
        pid = table[jnp.arange(B), pos_vec // ps]
        pid = jnp.where(active, pid, 0)
        off = pos_vec % ps
        k_pool = k_pool.at[pid, :, off].set(k[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[pid, :, off].set(v[:, 0].astype(v_pool.dtype))
        ck = self.policy.constrain(self._pool_view(k_pool, table), "kv_cache")
        cv = self.policy.constrain(self._pool_view(v_pool, table), "kv_cache")
        o = attn_mod.attend_decode(q, ck, cv, pos_vec)
        o = self.policy.constrain(o, "heads")
        out = o.reshape(B, 1, -1) @ w["attn"]["wo"]
        return x + out, k_pool, v_pool

    def _attn_prefill_paged_step(self, w, x, k_pool, v_pool, table, pos,
                                 valid_len):
        """Layer-major prefill chunk against the page pool.

        x: (B, T, d) at absolute positions pos..pos+T-1; padded-tail
        positions (>= ``valid_len``) scatter to the null page — the paged
        equivalent of the stacked step's keep-mask.
        """
        self.trace_counts["attn_prefill_paged"] += 1
        cfg = self.cfg
        B, T, _ = x.shape
        ps = k_pool.shape[2]
        positions = (pos + jnp.arange(T)[None, :]) * jnp.ones((B, 1),
                                                              jnp.int32)
        h = rmsnorm(x, w["ln1"], cfg.norm_eps)
        q, k, v = attn_mod.qkv_project(w["attn"], cfg, h, positions)
        q = self.policy.constrain(q, "heads")
        tpos = pos + jnp.arange(T)
        valid = jnp.arange(T) < valid_len
        pid = jnp.where(valid[None, :], table[:, tpos // ps], 0)
        off = jnp.broadcast_to((tpos % ps)[None, :], (B, T))
        k_pool = k_pool.at[pid, :, off].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[pid, :, off].set(v.astype(v_pool.dtype))
        ck = self.policy.constrain(self._pool_view(k_pool, table), "kv_cache")
        cv = self.policy.constrain(self._pool_view(v_pool, table), "kv_cache")
        o = attn_mod.attend_cached(q, ck, cv, pos)
        o = self.policy.constrain(o, "heads")
        out = o.reshape(B, T, -1) @ w["attn"]["wo"]
        return x + out, k_pool, v_pool

    def _fold_page_step(self, k_pool, v_pool, kp, vp, pid):
        """Land ONE restored block's staged page data in the pools — the
        demand-stream fold for kv_page shards (pid traced, one executable
        for every fault)."""
        self.trace_counts["fold_page"] += 1
        return (k_pool.at[pid].set(kp.astype(k_pool.dtype)),
                v_pool.at[pid].set(vp.astype(v_pool.dtype)))

    # ------------------------------------------------------------ ffn/moe
    def ffn_step(self, w, x, streamed=False):
        """``streamed`` is a static argument, so it is normalised HERE —
        shapes and kernel availability are host-known — before touching the
        jit cache: where the Pallas path can't run (non-TPU without the
        opt-in, or non-dividing blocks) a streamed placement compiles to
        the very same executable as a pinned one. Without this, a live
        re-plan that newly streams FFNs (``rebind``, DESIGN.md §8) would
        trace a redundant variant of an identical computation."""
        streamed = streamed and self._streamed_mm_ok(x.shape, w["ffn"])
        self.ffn_paths[self._ffn_path(w["ffn"]) if streamed else "jnp"] += 1
        return self._ffn_step_jit(w, x, streamed=streamed)

    @staticmethod
    def _ffn_path(p) -> str:
        dt = p["w_up"].dtype
        if dt == jnp.uint8:
            return "pallas_int4"
        return "pallas_int8" if dt == jnp.int8 else "pallas_bf16"

    def _ffn_step(self, w, x, streamed=False):
        self.trace_counts["ffn"] += 1
        cfg = self.cfg
        h = rmsnorm(x, w["ln2"], cfg.norm_eps)
        if streamed and self._streamed_mm_ok(h.shape, w["ffn"]):
            h = self._ffn_streamed(w["ffn"], h)
        else:
            h = mlp_mod.ffn(w["ffn"], cfg, h, self.policy)
        return x + h

    def _moe_step(self, w, x):
        self.trace_counts["moe"] += 1
        cfg = self.cfg
        h = rmsnorm(x, w["ln2"], cfg.norm_eps)
        h = mlp_mod.moe_ffn(w["moe"], cfg, h, self.policy)
        return x + h

    def _moe_prefill_step(self, w, x, valid_len):
        """Monolithic MoE for a layer-major prefill chunk (DESIGN.md §10):
        positions >= ``valid_len`` (the padded tail) are routed to an
        out-of-range expert id so they claim no dispatch capacity and
        contribute nothing to the combine — a padded chunk is bit-identical
        to the unpadded one on its valid positions."""
        self.trace_counts["moe_prefill"] += 1
        cfg = self.cfg
        B, T, _ = x.shape
        valid = jnp.broadcast_to(jnp.arange(T)[None, :] < valid_len, (B, T))
        h = rmsnorm(x, w["ln2"], cfg.norm_eps)
        h = mlp_mod.moe_ffn(w["moe"], cfg, h, self.policy, valid=valid)
        return x + h

    # ------------------------------------------------ expert-granular moe
    # The monolithic ``moe_step`` splits into three jitted phases
    # (DESIGN.md §9) so the executor can demand-stream cold experts:
    #   route  -> top-k selection + capacity dispatch; the selected expert
    #             ids go back to the host, which requests ONLY those
    #             experts from the prefetcher;
    #   experts-> the (E, C, d) expert einsum against one GROUP's stacked
    #             weights (absent experts zero-filled). Called once for the
    #             pinned group — overlapping the cold-expert copies — and
    #             once for the streamed group. Both calls share one
    #             executable (same shapes), and each expert slice of the
    #             batched einsum depends only on its own weights, so the
    #             group split never changes a demanded expert's bits;
    #   combine-> jnp.where-merge of the two buffers by pinned membership,
    #             then the exact gather/gate/scatter of the monolithic
    #             path.
    # Every op matches ``moe_ffn`` one for one, so the phased path is
    # bit-identical to the monolithic sub-layer.
    def _moe_route_step(self, w, x):
        """w: {"router", "ln2"}; x: (B, T, d). Returns (disp, aux, idx)."""
        self.trace_counts["moe_route"] += 1
        cfg = self.cfg
        m = cfg.moe
        B, T, d = x.shape
        h = rmsnorm(x, w["ln2"], cfg.norm_eps).reshape(B * T, d)
        gates, idx, _ = mlp_mod._route(h, w["router"], m)
        cap = mlp_mod.capacity_of(B * T, m)
        disp, aux = mlp_mod.moe_dispatch(h, gates, idx, m, m.n_experts, 0,
                                         cap)
        return disp, aux, idx

    def _moe_route_prefill_step(self, w, x, valid_len):
        """Masked routing for a layer-major prefill chunk (DESIGN.md §10):
        identical to ``_moe_route_step`` except padded positions (>=
        ``valid_len``) route to expert id E — out of range, so they claim
        no capacity, never enter the demanded set the executor syncs to
        the host, and the combine gathers nothing for them. For a full
        chunk the mask is all-true and the maths is bit-identical."""
        self.trace_counts["moe_route_prefill"] += 1
        cfg = self.cfg
        m = cfg.moe
        B, T, d = x.shape
        valid = jnp.broadcast_to(jnp.arange(T)[None, :] < valid_len, (B, T))
        h = rmsnorm(x, w["ln2"], cfg.norm_eps).reshape(B * T, d)
        gates, idx, _ = mlp_mod._route(h, w["router"], m)
        idx = jnp.where(valid.reshape(B * T)[:, None], idx, m.n_experts)
        cap = mlp_mod.capacity_of(B * T, m)
        disp, aux = mlp_mod.moe_dispatch(h, gates, idx, m, m.n_experts, 0,
                                         cap)
        return disp, aux, idx

    def _moe_experts_step(self, wstack, disp):
        """wstack: {"w_gate": (E,d,f), ...} with zeros outside the group."""
        self.trace_counts["moe_experts"] += 1
        return mlp_mod._expert_compute(disp, wstack, self.cfg)

    def _fold_expert_step(self, stack, tree, e):
        """Fold ONE expert's acquired weight tree into the (E, ...) group
        stack — a single dispatch for all weight keys, with the expert id
        traced so every fold shares one executable."""
        self.trace_counts["fold_expert"] += 1
        return {k: stack[k].at[e].set(tree[k]) for k in stack}

    def _moe_combine_step(self, x, buf_pinned, buf_streamed, pinned_mask,
                          aux):
        self.trace_counts["moe_combine"] += 1
        B, T, d = x.shape
        out_buf = jnp.where(pinned_mask[:, None, None], buf_pinned,
                            buf_streamed)
        out = mlp_mod.moe_combine(out_buf, aux, B * T, x.dtype)
        return x + out.reshape(B, T, d)

    def _streamed_mm_ok(self, xshape, p) -> bool:
        if not self.use_streamed_mm:
            return False
        B, T, d = xshape
        quant = p["w_up"].dtype in (jnp.int8, jnp.uint8)
        f = p["s_up"].shape[-1] if quant else p["w_up"].shape[1]
        m = B * T
        if not all(_blocks_divide(dim, blk)
                   for dim, blk in ((m, 128), (f, 128), (d, 128))):
            return False
        if not quant:
            return all(_blocks_divide(dim, blk)
                       for dim, blk in ((d, 512), (f, 512)))
        # fused-dequant kernels need each matrix's balanced quant groups to
        # tile its K dim exactly (and int4 groups to be even); otherwise
        # fall back to the jnp dequant path in models/mlp.py
        for name in ("w_gate", "w_up", "w_down"):
            if name not in p:
                continue
            K = f if name == "w_down" else d
            G = p[f"s{name[1:]}"].shape[0]
            g = -(-K // G)
            if g * G != K or (p[name].dtype == jnp.uint8 and g % 2):
                return False
        return True

    def _mm_dispatch(self, x2, p, name):
        """One matmul through the Pallas streamed kernel matching the
        weight's storage format — dequant fused into the k-loop for the
        quantised formats (DESIGN.md §11)."""
        w = p[name]
        if w.dtype == jnp.uint8:  # packed int4
            return streamed_matmul_int4(x2, w, p[f"s{name[1:]}"],
                                        p[f"z{name[1:]}"],
                                        interpret=self._mm_interpret)
        if w.dtype == jnp.int8:   # grouped int8
            s = p[f"s{name[1:]}"]
            block_k = -(-x2.shape[1] // s.shape[0])
            return streamed_matmul_int8(x2, w, s, block_k=block_k,
                                        interpret=self._mm_interpret)
        return streamed_matmul(x2, w, interpret=self._mm_interpret)

    def _ffn_streamed(self, p, h):
        """Dense FFN with all matmuls through the Pallas streamed kernel."""
        B, T, d = h.shape
        x2 = h.reshape(B * T, d)
        mm = self._mm_dispatch
        if self.cfg.mlp == "swiglu":
            hh = jax.nn.silu(mm(x2, p, "w_gate")) * mm(x2, p, "w_up")
        else:
            hh = jax.nn.gelu(mm(x2, p, "w_up"))
        hh = self.policy.constrain(hh.reshape(B, T, -1), "ffn_hidden")
        out = mm(hh.reshape(B * T, -1), p, "w_down")
        return out.reshape(B, T, d)

    # ------------------------------------------------------------ ends
    def _embed_step(self, embed, tokens):
        self.trace_counts["embed"] += 1
        return jnp.take(embed, tokens, axis=0)

    def _head_step(self, final_norm, unembed, x):
        """unembed: (d, V) — callers pass embed.T for tied embeddings."""
        self.trace_counts["head"] += 1
        x = rmsnorm(x, final_norm, self.cfg.norm_eps)
        return x @ unembed
