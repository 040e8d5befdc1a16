"""A configuration, a traffic mix and a metric reader added as files are
found by their names, with no edit to the harness."""
import json
import shutil
import types
from pathlib import Path

import harness

BENCH = Path(__file__).resolve().parents[1]


def test_new_files_are_found_by_name(tmp_path):
    (tmp_path / "bench").mkdir()
    for d in ("traffic", "metrics", "cost", "checks", "configs"):
        (tmp_path / "bench" / d).mkdir()
    shutil.copy(BENCH / "traffic" / "open_loop.py",
                tmp_path / "bench" / "traffic" / "open_loop.py")
    conf = {"arch": "qwen2.5-14b", "attention": "gqa", "mesh": [1, 1],
            "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "intermediate_size": 128, "vocab_size": 256, "rope_theta": 1e4,
            "rms_norm_eps": 1e-5, "serve": {"slots": 2, "cache_len": 64}}
    (tmp_path / "bench" / "configs" / "throwaway.json").write_text(
        json.dumps(conf))
    mix = {"kind": "open_loop", "rate": 2.0, "ramp_s": 1, "drain_s": 2,
           "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16,
                      "round_up_to": [8, 16]},
           "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}
    (tmp_path / "bench" / "traffic" / "burst-x.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "throwaway_ms.x.py").write_text(
        "def read(ctx):\n    return 42.0 * ctx.scale\n")
    manifest = {
        "configs": [{"name": "tw", "file": "bench/configs/throwaway.json"}],
        "workloads": [{"name": "tw.burst", "config": "tw",
                       "traffic": "burst-x", "chips": 1}],
        "end_to_end": [], "per_layer": [
            {"name": "throwaway_ms.x", "unit": "ms",
             "workloads": ["tw.burst"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    layout = harness.Layout(tmp_path)
    w = layout.workload("tw.burst")
    assert layout.config(w["config"])["hidden_size"] == 64
    p = layout.traffic(w["traffic"])
    gen = layout.generator(p["kind"]).Traffic(p, 2**33 + 5, 3.0, 256, 2)
    assert gen.prompt_lengths() == [8, 16]
    # ramp, window and tail hold rate x length arrivals each
    assert len(gen.plan) == 2 + 6 + 4
    assert sum(m for *_, m in gen.plan) == 6
    names = [m["name"] for m in layout.metrics_for("tw.burst", trace=True)]
    assert names == ["throwaway_ms.x"]
    reader = layout.metric("throwaway_ms.x")
    assert reader.read(types.SimpleNamespace(scale=2)) == 84.0
    cfg = harness.program_config(layout.config("tw"))
    assert (cfg.d_model, cfg.n_layers, cfg.vocab) == (64, 2, 256)


def test_every_seed_sends_the_same_schedule():
    # a chat mix: lognormal prompts (median 512) and answers (median 128)
    p = {"kind": "open_loop", "rate": 3.0, "ramp_s": 10, "drain_s": 60,
         "prompt": {"median": 512, "sigma": 1.0, "min": 64, "max": 2048,
                    "round_up_to": [128, 256, 512, 1024, 2048]},
         "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 512}}
    mod = harness.load_module(BENCH / "traffic" / "open_loop.py", "ol")
    a = mod.Traffic(p, 1, 30.0, 152064, 32).plan
    b = mod.Traffic(p, 2**31 + 77, 30.0, 152064, 32).plan
    assert a == b
    # ramp, window and tail hold rate x length arrivals each; the window's
    # start at the ramp's end
    n = round(p["rate"] * 30.0)
    t = [x[0] for x in a if x[3]]
    assert len(t) == n and t[0] == p["ramp_s"]
    assert max(t) < p["ramp_s"] + 30.0
    # half the prompts lie below the lognormal's median of 512
    assert sum(x[1] <= 512 for x in a if x[3]) == n // 2
