"""A configuration file, and what any family's weights are drawn with.

The weights are the benchmark's, not the program's: a family draws them
from ``--seed`` (``families/<family>.py``, ``make_weights``) and they are
handed both to ``Session.open(params=...)`` and to the plain reference.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

NORM_STD = 0.1      # norm scales are 1 + N(0, NORM_STD)
BIAS_STD = 0.02


def load_config(path) -> dict:
    """The configuration file, checked by the family it names."""
    from chipbench import families
    raw = json.loads(Path(path).read_text())
    families.load(raw.get("family")).check(raw)
    return raw


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


def weight_bytes(cfg) -> int:
    """The planner's weight bytes of a configuration (the HBM budget is a
    multiple of it): every sub-layer, the embedding and the head."""
    from repro.core import build_graph
    return sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
