"""The harness driven end to end on the CPU at a test size: the chip
check, the reference against the program, and ``correct`` under a clean
run and under each fault a served cell can have. The control's readings
at the cells' own sizes come from ``calibrate.py`` on the chip; here it
runs at the test size."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
for p in (CHIP, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import harness, model, reference, traffic  # noqa: E402
from chipbench.families import dense  # noqa: E402

TESTDATA = CHIP / "testdata"
CELL = "yi-9b-l8.tight.batch8"


# ---------------------------------------------------------------- faults
def fault_token(session, monkeypatch):
    """Every served token replaced by its successor in the vocabulary
    where the serving loop picks it."""
    import repro.core.serving as serving
    pick, vocab = serving.greedy_token, session.cfg.vocab
    monkeypatch.setattr(serving, "greedy_token",
                        lambda logits: (pick(logits) + 1) % vocab)


def fault_state(session, monkeypatch):
    """The decode step hands back the KV cache it was given, unchanged."""
    eng = session.executor.engine
    step = eng.attn_decode_step

    def stale(w, x, k, v, *rest):
        return step(w, x, k, v, *rest)[0], k, v

    monkeypatch.setattr(eng, "attn_decode_step", stale)


def fault_half_batch(session, monkeypatch):
    """Each fused decode pass runs with the second half of its active
    slots masked out."""
    import jax.numpy as jnp
    ex = session.executor
    decode = ex._run_decode

    def half(tokens, kv, pos_vec, active, n_active):
        act = np.asarray(active).copy()
        idx = np.flatnonzero(act)
        if len(idx) > 1:
            act[idx[len(idx) // 2:]] = False
        return decode(tokens, kv, pos_vec, jnp.asarray(act), n_active)

    monkeypatch.setattr(ex, "_run_decode", half)


FAULTS = {"token": fault_token, "state": fault_state,
          "half_batch": fault_half_batch}


def _cell(config="smoke-dense.json"):
    raw = model.load_config(TESTDATA / config)
    spec = traffic.load(TESTDATA / "smoke.traffic.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell("smoke", 1, raw, spec, bench["end_to_end"], [],
                        raw["check"][harness.CHECK])


class Ticks:
    """A clock that advances a fixed step at every reading, so that a
    window holds the same steps whatever the load of the test machine."""

    def __init__(self, step=0.005):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def _run(seed, cell=None, **kw):
    from repro.core.system import TPU_V5E
    return harness.run_cell(cell or _cell(), seed, 1.0, False,
                            t_process=time.perf_counter(), system=TPU_V5E,
                            device=None, peaks=None, clock=Ticks(),
                            **kw).result


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py",
                           "--workload", CELL, "--seed", "3", "--seconds",
                           "1", *args], cwd=cwd, env=env, text=True,
                          capture_output=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_reference_matches_session_logits():
    """The program's bf16 logits at smoke width, prefill then decode
    through the cache, against the float32 reference on the same
    tokens."""
    import jax.numpy as jnp
    from repro import Session
    from repro.core import InferenceSetting
    from repro.core.system import TPU_V5E
    raw = model.load_config(TESTDATA / "smoke-dense.json")
    cfg = dense.model_config(raw)
    params = dense.make_weights(raw, 2**35 + 1)
    sess = Session.open(cfg, TPU_V5E, 10**9, InferenceSetting(
        batch=1, context=64), max_seq=64, params=params)
    ex = sess.executor
    seen = []
    head = ex.engine.head_step

    def capture(*a):
        out = head(*a)
        seen.append(np.asarray(out, np.float32)[0, -1])
        return out

    ex.engine.head_step = capture
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, 23)
    served = sess.generate(jnp.asarray(prompt[None], jnp.int32),
                           max_new_tokens=6)[0]
    got = np.stack(seen[:len(served)])
    # generate() feeds the prefill's greedy token first: the reference is
    # teacher-forced on those served tokens
    first = int(np.argmax(got[0]))
    tokens = [first] + [int(t) for t in served[:-1]]
    ref = dense.logits_at(raw, params, [(prompt, tokens)])[0]
    assert ref.shape == got.shape
    scale = float(np.abs(ref).max())
    assert float(np.abs(got - ref).max()) <= 0.02 * scale
    assert (np.argmax(ref, -1) == np.argmax(got, -1)).mean() >= 0.8


def test_reference_gap_of_its_own_argmax_is_zero():
    raw = model.load_config(TESTDATA / "smoke-dense.json")
    params = dense.make_weights(raw, 9)
    prompt = np.arange(11) % raw["vocab_size"]
    ref = dense.logits_at(raw, params, [(prompt, [1, 2, 3, 4])])[0]
    assert np.all(reference.gaps(ref, ref.argmax(-1)) == 0)
    assert np.all(reference.gaps(ref, ref.argmin(-1)) > 0)


def test_clean_run_is_correct():
    r = _run(2**33 + 7)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["window"]["compiles"] == 0
    assert set(r["metrics"]) == {"ttft_p50_ms", "itl_p95_ms",
                                 "output_tokens_per_s", "setup_s"}
    assert list(r)[-1] == "check"
    assert r["check"]["mean_logit_gap"]["value"] <= \
        r["check"]["mean_logit_gap"]["limit"]
    assert r["window"]["max_logit_gap"] >= \
        r["check"]["mean_logit_gap"]["value"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    """A token altered where it is produced, a decode step that returns
    its cache unchanged, half of the fused batch left out. (The exchange
    between chips does not exist in a one-chip cell.)"""
    r = _run(2**33 + 8, fault=lambda s: FAULTS[fault](s, monkeypatch))
    assert not r["correct"]
    assert r["check"]["mean_logit_gap"]["value"] > \
        r["check"]["mean_logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [1, 2**33 + 2, 3])
def test_control_fails_the_limit(seed):
    """The control, the reference computed with int8 operands, at the
    test size on fixed sequences: the tokens it puts first lie further
    below the reference's best, on the mean, than the limit allows, while
    the reference's own picks read 0."""
    raw = model.load_config(TESTDATA / "test-dense.json")
    params = dense.make_weights(raw, seed)
    rng = np.random.default_rng(seed)
    seqs = [(rng.integers(0, raw["vocab_size"], 40),
             list(rng.integers(0, raw["vocab_size"], 24))) for _ in range(6)]
    ref = dense.logits_at(raw, params, seqs)
    low = dense.logits_at(raw, params, seqs, low="int8")
    control = np.concatenate([reference.gaps(r, c.argmax(-1))
                              for r, c in zip(ref, low)])
    own = np.concatenate([reference.gaps(r, r.argmax(-1)) for r in ref])
    assert own.max() == 0.0
    assert control.mean() > raw["check"]["mean_logit_gap"]
