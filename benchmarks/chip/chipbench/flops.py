"""Operations and bytes a dense decoder's work needs, from its shapes.

Counts are what the algorithm needs for the valid tokens of a call:
padding rows, masked cache positions and inactive decode slots are not
counted, so a share of the roofline built on them cannot pass 100% by a
later change that stops computing them. A multiply-add is two
operations; weights and the KV cache are bfloat16 (2 bytes).
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


@dataclass(frozen=True)
class Shapes:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    qkv_bias: bool
    tied: bool

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    # ---- weights
    @property
    def attn_params(self) -> int:
        p = 2 * self.d * self.q_dim + 2 * self.d * self.kv_dim + self.d
        if self.qkv_bias:
            p += self.q_dim + 2 * self.kv_dim
        return p

    @property
    def ffn_params(self) -> int:
        return 3 * self.d * self.d_ff + self.d


def shapes_of(cfg) -> Shapes:
    """From a ``repro`` ModelConfig (or anything with its attributes)."""
    return Shapes(d=cfg.d_model, heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                  head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
                  vocab=cfg.vocab, layers=cfg.n_layers,
                  qkv_bias=cfg.qkv_bias, tied=cfg.tie_embeddings)


# ---------------------------------------------------------------- ffn
def ffn_flops(s: Shapes, rows: int) -> float:
    """SwiGLU FFN over ``rows`` tokens: gate, up and down matmuls."""
    return 2.0 * rows * 3 * s.d * s.d_ff


def ffn_bytes(s: Shapes, rows: int) -> float:
    """The weights once, plus the residual read and written."""
    return float(s.ffn_params * BF16 + 2 * rows * s.d * BF16)


# ---------------------------------------------------------------- attn
def attn_decode_flops(s: Shapes, contexts) -> float:
    """One fused decode attention call; ``contexts`` lists, per active
    slot, the cache positions its new token attends to (itself included):
    projections, scores and the weighted sum."""
    n = len(contexts)
    proj = 2.0 * n * (s.d * (s.q_dim + 2 * s.kv_dim) + s.q_dim * s.d)
    attn = sum(4.0 * s.q_dim * c for c in contexts)
    return proj + attn


def attn_decode_bytes(s: Shapes, contexts) -> float:
    """Weights once, each active slot's valid K and V read, its new K and
    V row written, its residual read and written."""
    n = len(contexts)
    kv_read = sum(2 * s.kv_dim * c * BF16 for c in contexts)
    return float(s.attn_params * BF16 + kv_read
                 + n * (2 * s.kv_dim + 2 * s.d) * BF16)


# ---------------------------------------------------------------- model
def token_flops(s: Shapes, context: int) -> float:
    """Forward operations of one token that attends to ``context``
    positions: every layer's matmuls and attention, and the output head.
    The embedding lookup is a gather and counts nothing."""
    per_layer = (2.0 * (s.d * (s.q_dim + 2 * s.kv_dim) + s.q_dim * s.d)
                 + 4.0 * s.q_dim * context + 2.0 * 3 * s.d * s.d_ff)
    return s.layers * per_layer + 2.0 * s.d * s.vocab


def prompt_flops(s: Shapes, length: int) -> float:
    """A causal prefill of ``length`` tokens; only the last position goes
    through the head (the program computes no other logits)."""
    per_layer_fixed = (2.0 * (s.d * (s.q_dim + 2 * s.kv_dim) + s.q_dim * s.d)
                       + 2.0 * 3 * s.d * s.d_ff)
    attn = 4.0 * s.q_dim * length * (length + 1) / 2
    return (s.layers * (length * per_layer_fixed + attn)
            + 2.0 * s.d * s.vocab)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes: float) -> float:
    """Least time the chip could take for one call."""
    return max(flops / peak_flops, nbytes / peak_bytes)
