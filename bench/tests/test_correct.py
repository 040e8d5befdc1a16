"""What decides ``correct``, driven through the whole harness at a size a
CPU test can hold (the chip check skipped): a sound run passes, the
float8 control put in the program's place makes ``correct`` false, and
so does a token altered where the decode step produces it."""
import json
import shutil
from pathlib import Path

import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 4242
# At this size sound runs read a logit gap of ~4e-4 and the float8
# control ~0.06 (CPU runs while this test was written); the limit sits
# between them.
LIMIT = 0.01

TINY = {"hidden_size": 512, "num_hidden_layers": 4, "intermediate_size": 1024,
        "vocab_size": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "torch_dtype": "bfloat16", "mesh": [1, 1], "chips": 1}
CELLS = {
    "tiny.closed": (
        dict(TINY, arch="minicpm3-4b", attention="mla",
             num_attention_heads=4, num_key_value_heads=4, q_lora_rank=64,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16,
             serve={"slots": 4, "cache_len": 256, "max_prefill_batch": 1}),
        {"kind": "closed_loop", "clients": 4, "prompt_lengths": [32, 96],
         "output_tokens": 24, "drain_s": 60, "check_requests": 2}),
    "tiny.open": (
        dict(TINY, arch="qwen2.5-14b", attention="gqa",
             num_attention_heads=4, num_key_value_heads=2, head_dim=128,
             attention_bias=True,
             serve={"slots": 4, "cache_len": 256, "max_prefill_batch": 2}),
        {"kind": "open_loop", "rate": 4.0, "ramp_s": 1, "drain_s": 20,
         "prompt": {"median": 48, "sigma": 1.0, "min": 16, "max": 128,
                    "round_up_to": [32, 64, 128]},
         "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 24},
         "check_requests": 3}),
}


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for name, (conf, mix) in CELLS.items():
        cname, mname = name + "-config", name + "-mix"
        (root / "bench" / "configs" / f"{cname}.json").write_text(
            json.dumps(conf))
        (root / "bench" / "traffic" / f"{mname}.json").write_text(
            json.dumps(mix))
        (root / "bench" / "checks" / f"{name}.json").write_text(
            json.dumps({"logit_gap": {"limit": LIMIT}}))
        manifest["configs"].append(
            {"name": cname, "file": f"bench/configs/{cname}.json"})
        manifest["workloads"].append(
            {"name": name, "config": cname, "traffic": mname, "chips": 1})
    twin = {"minicpm3-4b.longctx-decode": "tiny.closed",
            "qwen2.5-14b.tp4-chat": "tiny.open"}
    # every reader: the offline ones on the closed loop, the serving
    # ones (whose cell is not in BENCHMARK.json yet) on the open loop
    manifest["end_to_end"] = [m for m in manifest["end_to_end"]
                              if "workloads" not in m]
    manifest["per_layer"] = [
        {"name": f.stem, "unit": "%" if any(
            w in f.stem for w in ("share", "occupancy", "busy", "roofline",
                                  "mfu")) else "ms",
         "workloads": ["tiny.open" if f.stem.endswith(".serve")
                       else "tiny.closed"]}
        for f in sorted((BENCH / "metrics").glob("*.*.py"))]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return harness.Layout(root)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_passes_and_the_control_fails(layout, cell):
    res = harness.run_cell(cell, SEED, 2.0, False, layout=layout,
                           require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["logit_gap"]["value"] <= LIMIT
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert list(res)[-1] == "checks"
    # the float8 control in the program's place, judged by the same check
    ctrl = harness.run_cell(cell, SEED + 1, 2.0, False, layout=layout,
                            require_tpu=False, control=True)
    assert not ctrl["correct"], ctrl["checks"]
    assert ctrl["failed"] == 0
    assert ctrl["checks"]["logit_gap"]["value"] > 3 * LIMIT
    assert ctrl["served_logit_gap"] <= LIMIT
    assert list(ctrl)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_altered_token_makes_the_run_incorrect(layout, cell, monkeypatch):
    from repro.serve import engine

    vocab = CELLS[cell][0]["vocab_size"]
    make = engine.make_jit_steps

    def broken(*a, **kw):
        steps = make(*a, **kw)
        decode = steps["decode"]

        def altered(*args):
            tok, cache = decode(*args)
            return (tok + 1) % vocab, cache

        steps["decode"] = altered
        return steps

    monkeypatch.setattr(engine, "make_jit_steps", broken)
    res = harness.run_cell(cell, SEED, 2.0, False, layout=layout,
                           require_tpu=False)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_run_reads_every_per_layer_metric(layout, cell, monkeypatch):
    """The CPU has no device plane, so the profiler's trace is replaced
    by one device kept busy but for one gap per 100 ms of the window."""
    import devtrace

    def fake_reduce(self, tasks=()):
        offset = 1e6
        w0, w1 = self.marks[0] + offset, self.marks[1] + offset
        step = 1e8
        ops, mods = [], []
        t = w0
        while t + step <= w1:
            ops.append([t, 0.9 * step, "fusion.1"])
            ops.append([t + 0.5 * step, 0.1 * step, "all-reduce.1"])
            mods.append([t, 0.9 * step, "jit_decode_step(1)"
                         if len(mods) % 5 else "jit_prefill_step(2)"])
            t += step
        return devtrace.Trace([w0, w1], offset,
                              {"0": {"ops": ops, "modules": mods,
                                     "async": []}},
                              [[s + offset, e + offset, n]
                               for s, e, n in tasks])

    monkeypatch.setattr(devtrace.Capture, "start", lambda self: self._mark())
    monkeypatch.setattr(devtrace.Capture, "stop", lambda self: self._mark())
    monkeypatch.setattr(devtrace.Capture, "reduce", fake_reduce)
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    monkeypatch.setattr(harness.Layout, "peaks", lambda self, kind: peaks)
    res = harness.run_cell(cell, SEED, 3.0, True, layout=layout,
                           require_tpu=False)
    assert res["correct"]
    want = {m["name"] for m in layout.metrics_for(cell, trace=True)}
    assert set(res["metrics"]) == want
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"][0][0] == "fusion.1"
    for name, m in res["metrics"].items():
        if m["unit"] == "%":
            assert 0 <= m["value"] <= 105, (name, m)
