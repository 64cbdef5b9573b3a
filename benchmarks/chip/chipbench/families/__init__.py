"""Model families: one module each, found by the ``family`` that a
configuration file names (``configs/<name>.json``, key ``family``).

The program serves every family through ``repro.Session``; what the
benchmark needs of a model beyond that lives in ``families/<family>.py``,
which provides:

- ``check(raw)``: raises ``ValueError`` where the configuration file is
  not one this family runs;
- ``model_config(raw)``: the program's ``ModelConfig``;
- ``make_weights(raw, seed)``: the host parameter tree that
  ``Session.open(params=...)`` takes, drawn from the seed;
- ``logits_at(raw, params, seqs, low=None, length=0, rows=0)``: the plain
  float32 reference, independent of the program: for each
  ``(prompt, served)`` pair the ``(len(served), vocab)`` logits that chose
  each served token, teacher-forced; ``low`` names one of
  ``chipbench.reference.LOW``, the controls ``calibrate.py`` reads;
- ``shapes_of(cfg)``: what its counts take;
- ``prompt_flops(shapes, length)`` and ``token_flops(shapes, context)``:
  forward operations of a prefilled prompt and of a decoded token;
- ``KERNELS``: for each executable whose roofline a metric reads,
  ``work(shapes, prompts, decoded) -> [(calls, flops, bytes), ...]`` for
  one serving step that prefilled ``prompts`` (their lengths) and decoded
  tokens attending to ``decoded`` cache positions each.

Shared arithmetic lives beside the families: ``chipbench.model`` (seeded
draws, the planner's weight bytes), ``chipbench.reference`` (matmuls at
full precision, norms, rotary positions, attention, the low-precision
roundings, logit gaps) and ``chipbench.flops`` (the roofline). A new
family is a new file here; no other file of the harness changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import pkgutil


def names() -> list:
    """The families there are: every module on this package's path."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def load(family: str):
    """The module of ``family``; an unknown one is an error that lists
    the families there are."""
    if not (isinstance(family, str) and family.isidentifier()
            and importlib.util.find_spec(f"{__name__}.{family}")):
        raise ValueError(f"no model family {family!r}; families: "
                         f"{', '.join(names())}")
    return importlib.import_module(f"{__name__}.{family}")
