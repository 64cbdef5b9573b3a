"""Dense SwiGLU decoders (Qwen2 / Llama layout): the configuration's
``ModelConfig``, its random weights, the plain float32 reference and the
operations and bytes its work needs.

The reference is written from the published architecture and independent
of the program: pre-norm RMSNorm, grouped-query attention with rotary
positions (the half-split rotation), optional q/k/v biases, a SwiGLU MLP,
a final RMSNorm and a tied or separate output head. Every matmul runs at
``Precision.HIGHEST``, so the chip does not round it to bfloat16. It runs
layer by layer over a block of sequences, so only one layer's weights and
the block's residuals are on the device at a time.

Counts are what the algorithm needs for the valid tokens of a call:
padding rows, masked cache positions and inactive decode slots are not
counted, so a share of the roofline built on them cannot pass 100% by a
later change that stops computing them. A multiply-add is two
operations; weights and the KV cache are bfloat16 (2 bytes).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

from chipbench.flops import BF16
from chipbench.model import _draw_tree, seed_key
from chipbench.reference import _attention, _low_mm, _rmsnorm, _rope


def check(raw: dict) -> None:
    if raw.get("hidden_act") != "silu":
        raise ValueError(f"{raw.get('name')}: the dense family runs SwiGLU "
                         f"decoders only (hidden_act 'silu')")


def model_config(raw: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import ModelConfig
    return ModelConfig(
        name=raw["name"], family="dense",
        n_layers=raw["num_hidden_layers"], d_model=raw["hidden_size"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"], d_ff=raw["intermediate_size"],
        vocab=raw["vocab_size"], head_dim=raw["head_dim"],
        qkv_bias=bool(raw["attention_bias"]), mlp="swiglu", pos="rope",
        rope_theta=float(raw["rope_theta"]),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        norm_eps=float(raw["rms_norm_eps"]), dtype="bfloat16",
        source=raw["source"])


# ---------------------------------------------------------------- weights
def layer_shapes(raw: dict) -> dict:
    d, f = raw["hidden_size"], raw["intermediate_size"]
    hd = raw["head_dim"]
    q, kv = raw["num_attention_heads"] * hd, raw["num_key_value_heads"] * hd
    attn = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    if raw["attention_bias"]:
        attn.update(bq=(q,), bk=(kv,), bv=(kv,))
    return {"ln1": (d,), "ln2": (d,), "attn": attn,
            "ffn": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}


def make_weights(raw: dict, seed: int) -> dict:
    """The parameter tree ``Session.open(params=...)`` takes, as host
    (numpy bfloat16) arrays: ``embed``, ``final_norm``, ``unembed`` when
    the head is untied, and ``layers`` stacked on a leading layer axis.
    Each layer is drawn on the device by one jitted call (the same
    executable for every layer) and copied to host memory, where this
    system keeps weights that do not fit the budget; the device never
    holds the whole model at once, so the peak it reports is the serving
    peak."""
    import jax
    d, V, L = raw["hidden_size"], raw["vocab_size"], raw["num_hidden_layers"]
    key = seed_key(seed)
    ends = {"embed": (V, d), "final_norm": (d,)}
    if not raw["tie_word_embeddings"]:
        ends["unembed"] = (d, V)
    shapes = layer_shapes(raw)
    draw_layer = jax.jit(lambda k: _draw_tree(k, shapes))
    params = jax.tree.map(np.asarray, jax.jit(
        lambda k: _draw_tree(k, ends))(jax.random.fold_in(key, 0)))
    layers = None
    for i in range(L):
        tree = draw_layer(jax.random.fold_in(key, 1 + i))
        if layers is None:
            layers = jax.tree.map(
                lambda a: np.empty((L,) + a.shape, a.dtype), tree)
        jax.tree.map(lambda dst, a: dst.__setitem__(i, np.asarray(a)),
                     layers, tree)
        del tree
    params["layers"] = layers
    return params


# ---------------------------------------------------------------- reference
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


# ---------------------------------------------------------------- counts
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


def ffn_flops(s: Shapes, rows: int) -> float:
    """SwiGLU FFN over ``rows`` tokens: gate, up and down matmuls."""
    return 2.0 * rows * 3 * s.d * s.d_ff


def ffn_bytes(s: Shapes, rows: int) -> float:
    """The weights once, plus the residual read and written."""
    return float(s.ffn_params * BF16 + 2 * rows * s.d * BF16)


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


def ffn_work(s: Shapes, prompts, decoded) -> list:
    """``_ffn_step``: in each layer, one call over every row of the step,
    each prompt it prefilled and each token it decoded."""
    n = sum(prompts) + len(decoded)
    return [(s.layers, ffn_flops(s, n), ffn_bytes(s, n))] if n else []


def attn_decode_work(s: Shapes, prompts, decoded) -> list:
    """``_attn_decode_step``: in each layer, one fused call over the
    step's decoded tokens."""
    if not decoded:
        return []
    return [(s.layers, attn_decode_flops(s, decoded),
             attn_decode_bytes(s, decoded))]


KERNELS = {"_ffn_step": ffn_work, "_attn_decode_step": attn_decode_work}
