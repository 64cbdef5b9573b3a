"""CPU guard for ``chip_smoke.py``: its phases at qwen2-0.5b's smoke width
(Pallas in interpret mode), and its refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_smoke_config
from repro.core import TPU_V5E, run_install, system_for_device_kind

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen2-0.5b")


@pytest.fixture(scope="module")
def db():
    return run_install(TPU_V5E, quick=True)


def test_bf16_phases_agree_across_budgets(smoke, cfg, db):
    tokens, _ = smoke.bf16_phases(cfg, TPU_V5E, db)
    assert set(tokens) == {"ample", "tight", "swap"}
    assert len(tokens["ample"]) == smoke.BATCH
    assert all(len(t) == smoke.NEW_TOKENS for t in tokens["ample"])


@pytest.mark.parametrize("kv_layout", ["stacked", "paged"])
def test_donation_phase_fails_one_request(smoke, cfg, db, kv_layout):
    assert smoke.donation_phase(cfg, TPU_V5E, db, None,
                                kv_layout=kv_layout) == [1]


def test_int4_phase_takes_the_fused_kernel(smoke, cfg, db, monkeypatch):
    monkeypatch.setenv("REPRO_STREAMED_FFN", "1")   # interpret-mode Pallas
    tokens = smoke.int4_phase(cfg, TPU_V5E, db)
    assert len(tokens) == smoke.BATCH


def test_int4_phase_fails_without_the_kernel(smoke, cfg, db, monkeypatch):
    monkeypatch.delenv("REPRO_STREAMED_FFN", raising=False)
    with pytest.raises(smoke.PhaseFailed, match="pallas_int4"):
        smoke.int4_phase(cfg, TPU_V5E, db)


def test_kernel_phase_interpret(smoke):
    errors = smoke.kernel_phase(bf16_shape=(256, 512),
                                int4_shapes=((256, 384),), m=16,
                                interpret=True)
    assert all(e <= smoke.KERNEL_TOL for e in errors.values())


def test_device_kind_picks_the_system_row():
    assert system_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="TPU v4"):
        system_for_device_kind("TPU v4")


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """A directory holding only the script cannot import the program."""
    (tmp_path / "chip_smoke.py").write_text(SCRIPT.read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
