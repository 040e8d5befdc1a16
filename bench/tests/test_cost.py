"""Cost functions and the peak table against hand arithmetic."""
import json
import types
from pathlib import Path

import pytest

import harness
from reference import Model

BENCH = Path(__file__).resolve().parents[1]


def _model(name, **change):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return Model(dict(conf, **change))


def _cost(name):
    return harness.load_module(BENCH / "cost" / f"{name}.py", f"cost_{name}")


def test_minicpm3_bytes():
    m, mla = _model("minicpm3-4b"), _cost("mla")
    # (kv_lora_rank 256 + rope 32) x 2 bytes x 62 layers
    assert mla.kv_bytes_per_token(m) == (256 + 32) * 2 * 62 == 35_712
    # as published, the output head is the embedding table: 4,073,875,968
    # parameters, which a decode step reads whole
    assert m.tied
    assert mla.weight_bytes(m) == 2 * 4_073_875_968 == 8_147_751_936
    assert mla.read_bytes(m) == 8_147_751_936
    # with a separate head, the weights measured on the chip when the
    # program ran it so; a decode step reads every matrix but the table
    untied = _model("minicpm3-4b", tie_word_embeddings=False)
    assert mla.weight_bytes(untied) == 8_523_805_696
    assert mla.read_bytes(untied) == 8_523_805_696 - 2 * 73448 * 2560


def test_qwen_tp4_bytes():
    m, gqa = _model("qwen2.5-14b-tp4"), _cost("gqa")
    # 2 (k, v) x 8 kv heads x 128 x 2 bytes x 48 layers / 4 chips
    assert gqa.kv_bytes_per_token(m, 4) == 2 * 8 * 128 * 2 * 48 // 4 == 49_152
    assert gqa.weight_bytes(m, 4) == 7_385_761_792
    assert gqa.weight_bytes(m, 1) == 29_540_067_328


def test_mla_decode_flops_by_hand():
    m, mla = _model("minicpm3-4b"), _cost("mla")
    # absorbed decode: scores over latent + rope (2 x 288), values over
    # the latent (2 x 256) per head and position
    assert mla.decode_attn_flops(m, 1) == 62 * 40 * 1088
    f, b = mla.decode_step(m, tokens=2, positions=100)
    assert f == 2 * mla.matmul_flops(m) + 62 * 40 * 1088 * 100
    assert b == mla.read_bytes(m) + 35_712 * 100


def _roofline_ctx(seconds_per_step, n_steps=10):
    m, mla = _model("minicpm3-4b"), _cost("mla")
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    # one request decoding 2 tokens per step inside the traced window
    rec = types.SimpleNamespace(out=[0] * 21, prompt=[0] * 1000,
                                t_first=0.0, t_done=1.9)
    tr = types.SimpleNamespace(
        window=[0.0, 2e9], offset_ns=0.0,
        module_time_mean=lambda names: (seconds_per_step * n_steps, n_steps))
    counts = [{"ticks": 0, "decode_tokens": 0},
              {"ticks": n_steps, "decode_tokens": 2 * n_steps}]
    return types.SimpleNamespace(trace=tr, peaks=peaks, model=m, cost=mla,
                                 records=[rec], config={"mesh": [1, 1]},
                                 trace_counts=counts)


def test_decode_roofline_is_at_most_100_by_construction():
    reader = harness.load_module(BENCH / "metrics" /
                                 "decode_roofline.offline.py", "roof")
    ctx = _roofline_ctx(1.0)
    m, mla, pk = ctx.model, ctx.cost, ctx.peaks
    # 20 decode tokens in the window over 10 steps: 2 per step
    pos = sum(1000 + j for j in range(1, 21)) / 10
    f, b = mla.decode_step(m, 2, pos)
    least = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    assert reader.read(_roofline_ctx(least)) == pytest.approx(100.0)
    assert reader.read(_roofline_ctx(2 * least)) == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    layout = harness.Layout()
    assert layout.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        layout.peaks("TPU v9 imaginary")
