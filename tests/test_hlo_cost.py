"""Validation of the trip-count-aware HLO cost model that the roofline
analysis (EXPERIMENTS §Methodology) rests on."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import HloCost, xla_cost_analysis


def _cost(fn, *specs):
    comp = jax.jit(fn).lower(*specs).compile()
    return HloCost(comp.as_text()).cost(), comp


def test_matches_hand_math_scan_free():
    def f(a, b, c):
        return jnp.tanh(a @ b) @ c

    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = jax.ShapeDtypeStruct((512, 64), jnp.float32)
    cost, comp = _cost(f, a, b, c)
    want = 2 * 128 * 256 * 512 + 128 * 512 + 2 * 128 * 512 * 64
    assert abs(cost.flops - want) / want < 0.01
    # bytes agree with XLA's own accounting on a scan-free module
    xla_bytes = float(xla_cost_analysis(comp).get("bytes accessed", 0))
    assert abs(cost.bytes - xla_bytes) / max(xla_bytes, 1) < 0.05


def test_multiplies_scan_trip_counts():
    def g(x, w):
        def body(x, _):
            return jnp.tanh(x @ w), ()

        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    cost, comp = _cost(g, x, w)
    want = 10 * (2 * 64 * 64 * 64 + 64 * 64)
    assert abs(cost.flops - want) / want < 0.01
    # XLA's analysis counts the body once — the whole reason we exist
    xla = float(xla_cost_analysis(comp).get("flops", 0))
    assert xla < cost.flops / 5


def test_nested_scans_compose():
    def h(x, w):
        def inner(x, _):
            return x @ w, ()

        def outer(x, _):
            y, _ = jax.lax.scan(inner, x, None, length=4)
            return y, ()

        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    cost, _ = _cost(h, x, w)
    want = 3 * 4 * (2 * 32 * 32 * 32)
    assert abs(cost.flops - want) / want < 0.05


@pytest.mark.slow
def test_collective_ring_model_and_promotion():
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_cost import HloCost
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("model",))
def f(x, w):
    y = jnp.einsum("bd,df->bf", x, w)
    return (y.astype(jnp.float32) ** 2).sum()
x = jax.ShapeDtypeStruct((8, 64), jnp.bfloat16)
w = jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
sx = NamedSharding(mesh, P(None, "model"))
sw = NamedSharding(mesh, P("model", None))
comp = jax.jit(f, in_shardings=(sx, sw)).lower(x, w).compile()
c = HloCost(comp.as_text()).cost()
assert "all-reduce" in c.coll_by_kind, c.coll_by_kind
# f32 result 8*32*4B = 1024B; promoted -> counted at bf16 (512B);
# ring AR: 2 * 512 * 3/4 = 768
assert abs(c.coll_bytes - 768) < 1, c.coll_bytes
print("COLL_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env, cwd=root,
                         timeout=300)
    assert "COLL_OK" in out.stdout, out.stderr[-1500:]
