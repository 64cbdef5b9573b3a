"""Unit tests of the chip benchmark's yardstick: traffic, metric
arithmetic, operation and byte counts, the peak table and the chip
check. All on the CPU, none needs a chip."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
for p in (CHIP, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import flops, harness, traffic  # noqa: E402
from chipbench.families import dense  # noqa: E402
from chipbench.loop import Sent, StepRecord  # noqa: E402
from chipbench.peaks import PEAKS, peaks_for  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})


# ---------------------------------------------------------------- traffic
@pytest.mark.parametrize("mix", MIXES)
def test_traffic_is_deterministic_per_seed(mix):
    spec = traffic.load(CHIP / "traffic" / f"{mix}.json")
    a = traffic.generate(spec, 1000, 2**33 + 17)
    b = traffic.generate(spec, 1000, 2**33 + 17)
    c = traffic.generate(spec, 1000, 2**33 + 18)
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes(mix):
    spec = traffic.load(CHIP / "traffic" / f"{mix}.json")
    sizes = [sorted((len(r.prompt), r.max_new_tokens)
                    for r in traffic.generate(spec, 1000, seed))
             for seed in (1, 2**40 + 3)]
    assert sizes[0] == sizes[1]
    lens = {len(r.prompt) for r in traffic.generate(spec, 1000, 5)}
    assert lens == set(spec.prompt_levels)
    assert max(lens) + spec.output_max == spec.max_seq


def test_prompt_levels_follow_the_lognormal_and_its_clip():
    levels = traffic.prompt_levels(512, 0.84, 128, 2048, 8)
    assert levels == tuple(sorted(levels))
    assert levels[3] < 512 < levels[4]           # the median sits mid-way
    assert traffic.prompt_levels(512, 5.0, 128, 2048, 8)[0] == 128
    assert traffic.prompt_levels(512, 5.0, 128, 2048, 8)[-1] == 2048


def test_warmup_covers_every_prompt_length_and_slot():
    spec = traffic.load(CHIP / "traffic" / "tight.chat1.json")
    warm = traffic.warmup(spec, 1000)
    assert {len(r.prompt) for r in warm} == set(spec.prompt_levels)
    assert len(warm) >= spec.clients


# ---------------------------------------------------------------- metrics
def _window(token_times, sent_at, start=10.0, end=20.0, steps=None,
            setup_s=5.0):
    sent = [Sent(rid=i, client=i, prompt_len=100, max_new_tokens=50,
                 sent_at=s, token_at=list(ts))
            for i, (ts, s) in enumerate(zip(token_times, sent_at))]
    return harness.Window(
        start=start, end=end, setup_s=setup_s, steps=steps or [],
        sent=sent, counters={}, decode_pass_streamed=[], peak_bytes=None,
        budget_bytes=1, shapes=None, peaks=None)


def test_ttft_counts_requests_whose_first_token_is_in_the_window():
    w = _window([[9.0, 10.5], [10.2], [12.0], [21.0]],
                [8.5, 10.0, 11.0, 19.0])
    # 9.0 lies before the window and 21.0 after it: 0.2 s and 1.0 s stay
    assert harness.reader("ttft_p50_ms")(w) == pytest.approx(600.0)


def _batch_tokens(stall_at=None, stall_s=0.0, n=8, tokens=20):
    """``n`` requests decoding together, a token every 0.1 s from 10.05,
    with one step that stalls them all at ``stall_at``."""
    times = []
    t = 10.05
    for _ in range(tokens):
        if stall_at is not None and abs(t - stall_at) < 1e-9:
            t += stall_s
        times.append(t)
        t = round(t + 0.1, 9)
    return [list(times) for _ in range(n)], [10.0] * n


def test_a_stall_in_the_window_moves_the_inter_token_tail():
    steady = _window(*_batch_tokens())
    assert harness.reader("itl_p95_ms")(steady) == pytest.approx(100.0)
    # one 2 s stall (an admission's prefill) hits all 8 requests: 8 of
    # 152 gaps, over 5%, so the 95th percentile moves
    stalled = _window(*_batch_tokens(stall_at=11.05, stall_s=2.0))
    gaps = [2.1] * 8 + [0.1] * 144
    assert harness.reader("itl_p95_ms")(stalled) == pytest.approx(
        1e3 * float(np.percentile(gaps, 95)))
    assert harness.reader("itl_p95_ms")(stalled) > 150.0
    # the same stall ending after the window closes counts for nothing
    late = _window(*_batch_tokens(stall_at=11.05, stall_s=2.0), end=12.0)
    assert harness.reader("itl_p95_ms")(late) == pytest.approx(100.0)


def test_tokens_per_s_counts_every_token_of_the_window():
    steps = [StepRecord(10.0 + i, 10.5 + i, first_tokens=1, decode_tokens=7,
                        errors=0) for i in range(10)]
    w = _window([], [], steps=steps, start=10.0, end=20.0)
    assert harness.reader("output_tokens_per_s")(w) == pytest.approx(8.0)
    assert harness.reader("serving.decode_batch_mean")(w) == 7
    assert harness.reader("serving.admit_step_share")(w) == pytest.approx(
        50.0)
    assert harness.reader("setup_s")(w) == 5.0


def test_pass_counters_per_pass():
    w = _window([], [])
    w.counters = {"decode_passes": 8, "prefill_passes": 2,
                  "streamed_bytes": 10**9, "at_use_bytes": 5 * 10**8,
                  "copy_s_exposed": 0.05, "prefetch_bytes": 4 * 10**9,
                  "prefetch_copy_s": 0.5}
    w.decode_pass_streamed = [10**8, 3 * 10**8]
    assert harness.reader("planner.streamed_mb_per_pass")(w) == 200.0
    assert harness.reader("executor.at_use_mb_per_pass")(w) == 50.0
    assert harness.reader("prefetch.exposed_copy_ms_per_pass")(w) == \
        pytest.approx(5.0)
    assert harness.reader("prefetch.link_gbps")(w) == pytest.approx(8.0)
    w.counters = dict(w.counters, prefetch_bytes=0, at_use_bytes=0,
                      streamed_bytes=0)
    w.decode_pass_streamed = [0, 0]
    for name in ("planner.streamed_mb_per_pass",
                 "executor.at_use_mb_per_pass",
                 "prefetch.exposed_copy_ms_per_pass", "prefetch.link_gbps"):
        assert harness.reader(name)(w) is None


def test_trace_readers_read_nothing_without_a_trace():
    w = _window([], [])
    for name in ("kernel.ffn_roofline", "kernel.attn_decode_roofline",
                 "device.idle_share", "model.mfu",
                 "planner.hbm_peak_of_budget"):
        assert harness.reader(name)(w) is None


def test_every_metric_has_a_reader_and_every_cell_its_files():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.reader(m["name"]))
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer


# ---------------------------------------------------------------- flops
QWEN = dense.Shapes(d=896, heads=14, kv_heads=2, head_dim=64, d_ff=4864,
                    vocab=151936, layers=24, qkv_bias=True, tied=True)
YI = dense.Shapes(d=4096, heads=32, kv_heads=4, head_dim=128, d_ff=11008,
                  vocab=64000, layers=8, qkv_bias=False, tied=False)


def test_ffn_counts_by_hand():
    # qwen2-0.5b: 3 * 896 * 4864 weights of 2 bytes, plus the norm
    assert dense.ffn_bytes(QWEN, 1) == 2 * (3 * 896 * 4864 + 896) \
        + 2 * 896 * 2
    assert dense.ffn_flops(QWEN, 8) == 2 * 8 * 3 * 896 * 4864
    # yi-9b: 135266304 weights per FFN
    assert dense.ffn_bytes(YI, 512) == 2 * (135266304 + 4096) \
        + 2 * 512 * 4096 * 2
    assert dense.ffn_flops(YI, 512) == pytest.approx(138.5e9, rel=1e-3)


def test_attention_decode_counts_by_hand():
    # yi-9b: q 4096x4096, k and v 4096x512, o 4096x4096
    params = 2 * 4096 * 4096 + 2 * 4096 * 512 + 4096
    assert YI.attn_params == params
    ctx = [100, 1]
    assert dense.attn_decode_bytes(YI, ctx) == (
        2 * params + 2 * 512 * 101 * 2 + 2 * (2 * 512 + 2 * 4096) * 2)
    assert dense.attn_decode_flops(YI, ctx) == (
        2 * 2 * (4096 * (4096 + 1024) + 4096 * 4096) + 4 * 4096 * 101)
    # qwen2-0.5b carries q, k and v biases
    assert QWEN.attn_params == (2 * 896 * 896 + 2 * 896 * 128 + 896
                                + 896 + 2 * 128)


def test_model_counts_by_hand():
    per_layer = (2 * (896 * (896 + 256) + 896 * 896) + 4 * 896 * 10
                 + 2 * 3 * 896 * 4864)
    assert dense.token_flops(QWEN, 10) == 24 * per_layer + 2 * 896 * 151936
    # a prompt of one token is one token at context 1
    assert dense.prompt_flops(YI, 1) == dense.token_flops(YI, 1)
    assert dense.prompt_flops(YI, 3) == pytest.approx(
        sum(dense.token_flops(YI, c) for c in (1, 2, 3))
        - 2 * 2 * 4096 * 64000)


def test_roofline_takes_the_slower_bound():
    p = PEAKS["TPU v5 lite"]
    assert flops.roofline_s(197e12, 0, p.bf16_flops, p.hbm_bytes) == 1.0
    assert flops.roofline_s(0, 819e9, p.bf16_flops, p.hbm_bytes) == 1.0


# ---------------------------------------------------------------- chip
def test_a_kind_without_a_peak_row_is_an_error():
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def _fake_devices(monkeypatch, platform, kind, n=1):
    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


@pytest.mark.parametrize("platform,kind,chips,ok", [
    ("cpu", "cpu", 1, False),
    ("tpu", "TPU v9 imaginary", 1, False),
    ("tpu", "TPU v5 lite", 4, False),
    ("tpu", "TPU v5 lite", 1, True),
])
def test_find_chip(monkeypatch, platform, kind, chips, ok):
    import run
    _fake_devices(monkeypatch, platform, kind)
    cell = types.SimpleNamespace(name="c", chips=chips)
    if ok:
        dev, peaks, system = run.find_chip(cell)
        assert system.name == "tpu-v5e" and peaks.hbm_bytes == 819e9
    else:
        with pytest.raises(LookupError):
            run.find_chip(cell)


# ---------------------------------------------------------------- steps
def _two_steps():
    """Step 1 (ends 10.5) prefills a 130- and a 7-token prompt and decodes
    one token of a request 5 tokens in; step 2 (ends 11.0) decodes one
    token of each of the three."""
    steps = [StepRecord(10.0, 10.5, 2, 1, 0), StepRecord(10.5, 11.0, 0, 3, 0)]
    sent = [Sent(0, 0, 130, 8, 9.9, token_at=[10.5, 11.0]),
            Sent(1, 1, 7, 8, 9.9, token_at=[10.5, 11.0]),
            Sent(2, 2, 20, 8, 8.0,
                 token_at=[8.1, 8.6, 9.1, 9.6, 10.1, 10.5, 11.0])]
    w = _window([], [], steps=steps)
    w.sent = sent
    return w


def test_step_tokens_reads_prefills_and_decode_contexts_from_stamps():
    # a decoded token i of a p-token prompt attends to p + i positions
    assert _two_steps().step_tokens() == [([130, 7], [25]),
                                          ([], [131, 8, 26])]


@pytest.mark.parametrize("name,module", [
    ("kernel.ffn_roofline", "_ffn_step"),
    ("kernel.attn_decode_roofline", "_attn_decode_step")])
def test_kernel_roofline_counts_each_step_once_per_layer(name, module):
    from chipbench.trace import TraceSummary
    w = _two_steps()
    w.family = dense
    w.shapes = dense.shapes_of(types.SimpleNamespace(
        d_model=64, n_heads=4, n_kv_heads=2, resolved_head_dim=16, d_ff=128,
        vocab=256, n_layers=3, qkv_bias=False, tie_embeddings=True))
    w.peaks = peaks_for("TPU v5 lite")
    s, p = w.shapes, w.peaks
    if module == "_ffn_step":
        need = sum(3 * flops.roofline_s(
            dense.ffn_flops(s, n), dense.ffn_bytes(s, n), p.bf16_flops,
            p.hbm_bytes) for n in (138, 3))
    else:
        need = sum(3 * flops.roofline_s(
            dense.attn_decode_flops(s, c), dense.attn_decode_bytes(s, c),
            p.bf16_flops, p.hbm_bytes) for c in ([25], [131, 8, 26]))
    w.trace = TraceSummary(window_s=1.0, busy_s=0.5, chips=1,
                           module_s={module: 2 * need})
    assert harness.reader(name)(w) == pytest.approx(50.0)
    w.trace.module_s = {}
    assert harness.reader(name)(w) is None


# ---------------------------------------------------------------- limits
def test_a_limit_lies_between_its_readings_nearer_the_upper():
    import calibrate
    lo, hi = 0.002, 0.02
    limit = calibrate.limit_between(lo, hi)
    assert lo < limit < hi
    assert limit / lo > hi / limit
    # readings less than three times apart give no limit
    assert calibrate.limit_between(0.066, 0.197) is None
    assert calibrate.limit_between(0.0, 0.1) is None


def test_a_cell_reads_its_limit_from_its_check_file(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CHECKS_DIR", tmp_path)
    assert harness.load_limit("some.cell") is None
    (tmp_path / "some.cell.json").write_text(json.dumps(
        {harness.CHECK: {"limit": 0.004, "lower": 0.001, "upper": 0.02}}))
    assert harness.load_limit("some.cell") == 0.004
