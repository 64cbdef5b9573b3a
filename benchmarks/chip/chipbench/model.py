"""A configuration file turned into the program's ``ModelConfig``, and the
cell's random weights made from the seed.

The weights are the benchmark's, not the program's: they are drawn here
from ``--seed`` and handed both to ``Session.open(params=...)`` and to the
plain reference. Each layer is drawn on the device by one jitted call (the
same executable for every layer) and copied to host memory, where this
system keeps weights that do not fit the budget; the device never holds
the whole model at once, so the peak it reports is the serving peak.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

NORM_STD = 0.1      # norm scales are 1 + N(0, NORM_STD)
BIAS_STD = 0.02


def load_config(path) -> dict:
    raw = json.loads(Path(path).read_text())
    if raw.get("family") != "dense" or raw.get("hidden_act") != "silu":
        raise ValueError(f"{path}: only dense SwiGLU decoders are run here")
    return raw


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


def layer_shapes(raw: dict) -> dict:
    d, f = raw["hidden_size"], raw["intermediate_size"]
    hd = raw["head_dim"]
    q, kv = raw["num_attention_heads"] * hd, raw["num_key_value_heads"] * hd
    attn = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    if raw["attention_bias"]:
        attn.update(bq=(q,), bk=(kv,), bv=(kv,))
    return {"ln1": (d,), "ln2": (d,), "attn": attn,
            "ffn": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}


def seed_key(seed: int):
    """A PRNG key from a seed of any size, 32 bits or more."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(key, shape, name):
    import jax
    import jax.numpy as jnp
    if name.startswith("ln") or name == "final_norm":
        x = 1.0 + NORM_STD * jax.random.normal(key, shape, jnp.float32)
    elif name.startswith("b"):
        x = BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        x = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[1])
    else:
        x = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
    return x.astype(jnp.bfloat16)


def _draw_tree(key, shapes):
    import jax
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        out[name] = (_draw_tree(k, shape) if isinstance(shape, dict)
                     else _draw(k, shape, name))
    return out


def make_weights(raw: dict, seed: int) -> dict:
    """The parameter tree ``Session.open(params=...)`` takes, as host
    (numpy bfloat16) arrays: ``embed``, ``final_norm``, ``unembed`` when
    the head is untied, and ``layers`` stacked on a leading layer axis."""
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


def weight_bytes(cfg) -> int:
    """The planner's weight bytes of a configuration (the HBM budget is a
    multiple of it): every sub-layer, the embedding and the head."""
    from repro.core import build_graph
    return sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
