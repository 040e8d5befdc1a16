"""Data pipeline, checkpointing, fault-tolerance substrates."""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_stub import given, settings, st

from repro.checkpoint import CheckpointManager, load_checkpoint, \
    save_checkpoint
from repro.core import UMTRuntime
from repro.data import (ShardedTokenSource, SyntheticTokenSource,
                        UMTPrefetcher, batch_for_step, write_token_shards)
from repro.ft import HeartbeatMonitor, StragglerDetector, plan_remesh


# ---------------------------------------------------------------- pipeline
def test_batch_for_step_deterministic():
    a = batch_for_step(7, seed=1, batch=8, seq=16, vocab=100, accum=2)
    b = batch_for_step(7, seed=1, batch=8, seq=16, vocab=100, accum=2)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = batch_for_step(8, seed=1, batch=8, seq=16, vocab=100, accum=2)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_sharded_source_resume_replays_stream(tmp_path):
    path = write_token_shards(str(tmp_path / "corpus"), n_shards=3,
                              tokens_per_shard=4096, vocab=97)
    src = ShardedTokenSource(path, batch=4, seq=31, accum=2)
    first = [src.fetch(s)["tokens"] for s in range(6)]
    src2 = ShardedTokenSource(path, batch=4, seq=31, accum=2)
    again = [src2.fetch(s)["tokens"] for s in range(6)]
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    # labels are the shifted tokens
    b0 = src.fetch(0)
    np.testing.assert_array_equal(b0["tokens"][0, 0, 1:],
                                  b0["labels"][0, 0, :-1])


def test_prefetcher_overlap_and_order(tmp_path):
    src = SyntheticTokenSource(seed=3, batch=4, seq=8, vocab=50)
    with UMTRuntime(n_cores=2) as rt:
        pf = UMTPrefetcher(src, rt, depth=3)
        for step in range(10):
            batch = pf.get(step)
            want = batch_for_step(step, seed=3, batch=4, seq=8, vocab=50)
            np.testing.assert_array_equal(batch["tokens"], want["tokens"])


def test_prefetcher_straggler_reissue():
    class SlowOnce:
        def __init__(self):
            self.calls = 0

        def fetch(self, step):
            self.calls += 1
            if step == 2 and self.calls <= 3:
                time.sleep(1.0)        # straggling fetch
            return {"tokens": np.full((1, 1), step)}

    src = SlowOnce()
    with UMTRuntime(n_cores=2) as rt:
        pf = UMTPrefetcher(src, rt, depth=1, reissue_after=0.15)
        for step in range(5):
            out = pf.get(step)
            assert out["tokens"][0, 0] == step
    assert pf.reissued >= 1


# -------------------------------------------------------------- checkpoint
def _tiny_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 8)),
                   "b": jnp.zeros((8,))},
        "step": jnp.asarray(5, jnp.int32),
    }


def test_checkpoint_roundtrip(tmp_path):
    state = _tiny_state()
    save_checkpoint(state, 5, str(tmp_path))
    loaded, step = load_checkpoint(str(tmp_path), state)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(loaded["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_checkpoint_async_via_umt(tmp_path):
    state = _tiny_state()
    with UMTRuntime(n_cores=2) as rt:
        w = save_checkpoint(state, 7, str(tmp_path), rt=rt, wait=False)
        w()
    loaded, step = load_checkpoint(str(tmp_path), state)
    assert step == 7


def test_checkpoint_detects_corruption(tmp_path):
    state = _tiny_state()
    save_checkpoint(state, 1, str(tmp_path))
    # flip a byte
    leaf = tmp_path / "step_000001" / "leaf_00000.npy"
    data = bytearray(leaf.read_bytes())
    data[0] ^= 0xFF
    leaf.write_bytes(bytes(data))
    with pytest.raises(IOError, match="checksum"):
        load_checkpoint(str(tmp_path), state)


def test_checkpoint_ignores_uncommitted_and_keeps_n(tmp_path):
    state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(state, s, wait=True)
    # only the last two survive
    assert mgr.latest_step() == 4
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_000003", "step_000004"]
    # a stale tmp dir must not be loadable
    os.makedirs(tmp_path / "step_000009.tmp")
    loaded, step = load_checkpoint(str(tmp_path), state)
    assert step == 4


def test_checkpoint_crash_mid_save_leaves_previous_intact(tmp_path):
    state = _tiny_state()
    save_checkpoint(state, 1, str(tmp_path))
    # simulate crash: partial tmp dir for step 2 without manifest
    os.makedirs(tmp_path / "step_000002.tmp")
    (tmp_path / "step_000002.tmp" / "leaf_00000.npy").write_bytes(b"junk")
    loaded, step = load_checkpoint(str(tmp_path), state)
    assert step == 1


RESHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax, numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_checkpoint, load_checkpoint
from repro.launch.mesh import make_mesh

path = sys.argv[1]
mesh_a = make_mesh((4, 2), ("data", "model"))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
xs = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
save_checkpoint({"x": xs}, 3, path)

mesh_b = make_mesh((2, 4), ("data", "model"))
sh = {"x": NamedSharding(mesh_b, P("data", "model"))}
loaded, step = load_checkpoint(path, {"x": x}, shardings=sh)
assert step == 3
np.testing.assert_array_equal(np.asarray(loaded["x"]), np.asarray(x))
assert loaded["x"].sharding.mesh.shape["data"] == 2
print("RESHARD_OK")
"""


def test_checkpoint_elastic_reshard_across_meshes(tmp_path):
    """Save on a (4,2) mesh, restore onto (2,4) — elastic restart."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", RESHARD_SCRIPT,
                          str(tmp_path)], capture_output=True, text=True,
                         env=env, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "RESHARD_OK" in out.stdout, out.stderr[-2000:]


# --------------------------------------------------------------------- ft
def test_heartbeat_detects_dead_hosts(tmp_path):
    hb = HeartbeatMonitor(str(tmp_path), n_hosts=4, timeout=0.3)
    for h in range(4):
        hb.beat(h)
    assert hb.dead() == []
    time.sleep(0.35)
    hb.beat(0)
    hb.beat(2)
    assert hb.dead() == [1, 3]


def test_straggler_detector_flags_persistent_slow_host():
    det = StragglerDetector(n_hosts=4, factor=2.0, window=4, patience=2)
    for step in range(5):
        for h in range(4):
            det.record(h, 1.0 if h != 2 else 5.0)
        flagged = det.check()
    assert flagged == [2]


def test_straggler_detector_recovers():
    det = StragglerDetector(n_hosts=4, factor=2.0, window=2, patience=1)
    for h in range(4):
        det.record(h, 10.0 if h == 3 else 1.0)
    assert det.check() == [3]
    for _ in range(3):
        for h in range(4):
            det.record(h, 1.0)
    assert det.check() == []


@given(alive=st.integers(1, 128), chips=st.sampled_from([4, 8]),
       model=st.sampled_from([4, 8, 16]))
@settings(max_examples=100, deadline=None)
def test_remesh_plan_invariants(alive, chips, model):
    plan = plan_remesh(alive, chips_per_host=chips,
                       old_mesh=(2, 16, model),
                       global_batch=256, micro_batch=32)
    used = 1
    for d in plan.new_mesh:
        used *= d
    if plan.valid:
        assert used <= alive * chips          # never oversubscribe
        assert plan.new_mesh[-1] == model     # TP axis preserved
        assert 256 % (plan.new_mesh[0] * plan.new_mesh[1]) == 0
    else:
        assert alive * chips < model


def test_remesh_shrink_example():
    plan = plan_remesh(96, chips_per_host=4, old_mesh=(2, 16, 16),
                       global_batch=256)
    assert plan.valid
    assert plan.new_mesh[-1] == 16
    assert plan.chips_used <= 384
