"""The serving entry point's own rules (``repro.launch.serve``): weights in
the compute dtype, weights created already sharded on a tensor-parallel
mesh, a failed request failing the command, and the compile-cache
choice."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch.serve import check_requests, serve_config
from repro.serve import Request


@pytest.mark.parametrize("arch", ["minicpm3-4b", "qwen2.5-14b"])
def test_serve_config_weights_in_compute_dtype(arch):
    cfg = serve_config(arch)
    assert jnp.dtype(cfg.dtype) == jnp.bfloat16
    assert jnp.dtype(cfg.param_dtype) == jnp.dtype(cfg.dtype)
    tiny = serve_config(arch, tiny=True)
    assert jnp.dtype(tiny.param_dtype) == jnp.float32
    assert (tiny.d_model, tiny.n_layers) != (cfg.d_model, cfg.n_layers)


def test_check_requests_names_the_failed_request():
    ok, bad = Request(0, np.zeros(4, np.int32)), Request(
        1, np.zeros(4, np.int32))
    check_requests([ok])
    bad.error = ValueError("prefill exploded")
    with pytest.raises(RuntimeError, match=r"1 of 2 requests failed; "
                                           r"request 1: .*prefill exploded"):
        check_requests([ok, bad])


def test_compile_cache_env_wins_else_repo_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.use_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


_SHARDED_INIT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.launch.serve import init_serve_params, serve_config
from repro.serve import param_shardings

cfg = serve_config("qwen2.5-14b", tiny=True)
mesh = make_mesh((1, 4), ("data", "model"))
got = init_serve_params(cfg, mesh, seed=3)
want = init_serve_params(cfg, None, seed=3)     # one device
sh = param_shardings(cfg, mesh)
split = 0
for g, w, s in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                   jax.tree.leaves(sh)):
    assert g.sharding.is_equivalent_to(s, g.ndim), (g.sharding, s)
    per = [x.data.nbytes for x in g.addressable_shards]
    split += all(p * 4 == g.nbytes for p in per)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
assert split >= 4, split
print("OK", split)
"""


@pytest.mark.slow
def test_init_serve_params_is_born_sharded_and_equals_unsharded_init():
    """On a (1, 4) mesh the weights come out of the init jit already laid
    out by ``param_shardings`` (a quarter of each split leaf per device,
    never whole on one), and hold the same values as the same seed's
    weights initialised on one device."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _SHARDED_INIT],
                       capture_output=True, text=True, env=env, cwd=root,
                       timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-3000:]


def test_negative_page_size_is_an_argument_error(capsys):
    """``--page-size`` takes 0 (auto) or a page size; a negative value
    is refused by the argument parser before anything is built."""
    from repro.launch.serve import serve

    with pytest.raises(SystemExit) as e:
        serve(["--page-size", "-1"])
    assert e.value.code == 2
    assert "--page-size" in capsys.readouterr().err
