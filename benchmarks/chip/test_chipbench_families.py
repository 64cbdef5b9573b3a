"""Model families: the dense family reproduces, bit for bit, what the
dense code gave before it became a family module
(``testdata/dense_identity.json``); an unknown family is an error that
names the families there are; and a family added as a file the harness
has never seen runs through the harness's own entry points. All on the
CPU."""
from __future__ import annotations

import hashlib
import importlib
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
for p in (CHIP, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import families, flops, harness, model, traffic  # noqa: E402
from chipbench.families import dense  # noqa: E402
from chipbench.loop import Sent, StepRecord  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.trace import TraceSummary  # noqa: E402

TESTDATA = CHIP / "testdata"
CONFIGS = CHIP / "configs"
IDENTITY = json.loads((TESTDATA / "dense_identity.json").read_text())


def _digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    leaves = sorted(jax.tree_util.tree_leaves_with_path(tree),
                    key=lambda kv: jax.tree_util.keystr(kv[0]))
    for path, leaf in leaves:
        a = np.ascontiguousarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------- dense
@pytest.mark.parametrize("key", sorted(IDENTITY["weights"]))
def test_dense_weights_are_unchanged(key):
    name, seed = key.split("/")
    raw = model.load_config(TESTDATA / f"{name}.json")
    assert families.load(raw["family"]) is dense
    assert _digest(dense.make_weights(raw, int(seed))) == \
        IDENTITY["weights"][key]


@pytest.mark.parametrize("low", [None, "int8"])
def test_dense_reference_and_control_logits_are_unchanged(low):
    raw = model.load_config(TESTDATA / "test-dense.json")
    params = dense.make_weights(raw, 2**33 + 1)
    rng = np.random.default_rng(7)
    seq = (rng.integers(0, raw["vocab_size"], 19),
           [int(t) for t in rng.integers(0, raw["vocab_size"], 5)])
    got = np.ascontiguousarray(dense.logits_at(raw, params, [seq],
                                               low=low)[0], np.float32)
    want = IDENTITY["logits"][str(low)]
    assert list(got.shape) == want["shape"]
    assert hashlib.sha256(got.tobytes()).hexdigest() == want["sha256"]


@pytest.mark.parametrize("name", sorted(IDENTITY["counts"]))
def test_dense_counts_and_weight_bytes_are_unchanged(name):
    raw = model.load_config(CONFIGS / f"{name}.json")
    cfg = dense.model_config(raw)
    s = dense.shapes_of(cfg)
    want = IDENTITY["counts"][name]
    ctx = [1, 100, 2049]
    assert dict(s.__dict__) == want["shapes"]
    assert s.attn_params == want["attn_params"]
    assert s.ffn_params == want["ffn_params"]
    assert [dense.prompt_flops(s, n) for n in (1, 141, 1857)] == \
        want["prompt_flops"]
    assert [dense.token_flops(s, c) for c in (1, 512, 1921)] == \
        want["token_flops"]
    assert [[dense.ffn_flops(s, n), dense.ffn_bytes(s, n)]
            for n in (1, 8, 1024)] == want["ffn"]
    assert [dense.attn_decode_flops(s, ctx),
            dense.attn_decode_bytes(s, ctx)] == want["attn_decode"]
    assert model.weight_bytes(cfg) == want["weight_bytes"]
    # the kernels' work per step is the same counts, once per layer
    assert dense.KERNELS["_ffn_step"](s, [141, 7], [30]) == [
        (s.layers, dense.ffn_flops(s, 149), dense.ffn_bytes(s, 149))]
    assert dense.KERNELS["_attn_decode_step"](s, [141], ctx) == [
        (s.layers, want["attn_decode"][0], want["attn_decode"][1])]
    assert dense.KERNELS["_attn_decode_step"](s, [141], []) == []


def test_an_unknown_family_is_an_error_that_names_the_families(tmp_path):
    assert "dense" in families.names()
    raw = json.loads((TESTDATA / "smoke-dense.json").read_text())
    for family in ("sparse_experts_someday", None, "../dense", "dense.x"):
        path = tmp_path / "c.json"
        path.write_text(json.dumps(dict(raw, family=family)))
        with pytest.raises(ValueError, match=r"families: .*\bdense\b"):
            model.load_config(path)


def test_the_dense_family_checks_its_configuration(tmp_path):
    raw = json.loads((TESTDATA / "smoke-dense.json").read_text())
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dict(raw, hidden_act="gelu")))
    with pytest.raises(ValueError, match="SwiGLU"):
        model.load_config(path)


# ---------------------------------------------------------------- new family
STUB = '''"""A family the harness has never seen: the dense arithmetic under
another name, with counts of its own, noting which entry points the
harness calls."""
from chipbench.families import dense

CALLS = []


def _noted(name, fn):
    def call(*a, **k):
        CALLS.append(name)
        return fn(*a, **k)
    return call


def check(raw):
    CALLS.append("check")
    if raw.get("stub_layout") != "dense":
        raise ValueError("not a stub configuration")


model_config = _noted("model_config", dense.model_config)
make_weights = _noted("make_weights", dense.make_weights)
logits_at = _noted("logits_at", dense.logits_at)
shapes_of = _noted("shapes_of", dense.shapes_of)


def prompt_flops(s, length):
    return 1e9 * length


def token_flops(s, context):
    return 1e9


def _stub_work(s, prompts, decoded):
    n = len(prompts) + len(decoded)
    return [(2, 1e6 * n, 1e3 * n)] if n else []


KERNELS = {"_stub_step": _stub_work}
'''


@pytest.fixture
def stub_family(tmp_path, monkeypatch):
    """``stub.py`` in a directory outside ``chipbench/``, on the path the
    harness looks families up in."""
    (tmp_path / "stub.py").write_text(STUB)
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    importlib.invalidate_caches()
    yield tmp_path
    sys.modules.pop("chipbench.families.stub", None)


class Ticks:
    """A clock that advances a fixed step at every reading."""

    def __init__(self, step=0.005):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def test_a_family_is_added_as_files_only(stub_family):
    from repro.core.system import TPU_V5E
    raw = json.loads((TESTDATA / "smoke-dense.json").read_text())
    path = stub_family / "stub-config.json"
    path.write_text(json.dumps(dict(raw, family="stub",
                                    stub_layout="dense")))
    assert "stub" in families.names()
    raw = model.load_config(path)
    stub = families.load(raw["family"])
    assert stub.__file__ == str(stub_family / "stub.py")
    assert stub.CALLS == ["check"]
    spec = traffic.load(TESTDATA / "smoke.traffic.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.Cell("stub", 1, raw, spec, bench["end_to_end"], [],
                        raw["check"][harness.CHECK])
    assert cell.family is stub
    r = harness.run_cell(cell, 2**33 + 11, 1.0, False,
                         t_process=time.perf_counter(), system=TPU_V5E,
                         device=None, peaks=None, clock=Ticks(),
                         controls=("int8",)).result
    assert r["correct"] and r["attempted"] > 0
    assert set(r["window"]["controls"]) == {"int8"}
    for name in ("model_config", "make_weights",
                 "shapes_of", "logits_at"):
        assert name in stub.CALLS, name

    # its counts reach the readers through the window
    steps = [StepRecord(10.0, 10.5, 1, 1, 0), StepRecord(10.5, 11.0, 0, 2, 0)]
    sent = [Sent(0, 0, 130, 8, 9.9, token_at=[10.5, 11.0]),
            Sent(1, 1, 20, 8, 8.0, token_at=[9.0, 10.5, 11.0])]
    w = harness.Window(
        start=10.0, end=11.0, setup_s=1.0, steps=steps, sent=sent,
        counters={}, decode_pass_streamed=[], peak_bytes=None,
        budget_bytes=1, shapes=stub.shapes_of(stub.model_config(raw)),
        peaks=peaks_for("TPU v5 lite"), family=stub,
        trace=TraceSummary(window_s=1.0, busy_s=0.5, chips=1,
                           module_s={"_stub_step": 1.0, "_ffn_step": 1.0}))
    # one prompt of 130 tokens and three decoded tokens in the window
    assert harness.reader("model.mfu")(w) == pytest.approx(
        100.0 * (130e9 + 3e9) / (1.0 * 197e12))
    p = w.peaks
    need = sum(2 * flops.roofline_s(1e6 * n, 1e3 * n, p.bf16_flops,
                                    p.hbm_bytes) for n in (2, 2))
    assert flops.kernel_roofline(w, "_stub_step") == pytest.approx(
        100.0 * need)
    # a kernel the family does not count reads nothing
    assert harness.reader("kernel.ffn_roofline")(w) is None
    assert harness.reader("kernel.attn_decode_roofline")(w) is None


def test_a_window_without_a_family_reads_no_counts():
    w = types.SimpleNamespace(peaks=peaks_for("TPU v5 lite"), family=None,
                              trace=TraceSummary(1.0, 0.5, 1,
                                                 {"_ffn_step": 1.0}))
    assert harness.reader("model.mfu")(w) is None
    assert flops.kernel_roofline(w, "_ffn_step") is None
