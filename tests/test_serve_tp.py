"""Tensor-parallel serving: sharded-vs-single bit-identity, collective
HLO, per-device KV footprint, and per-shard donation aliasing — all on a
forced 4-device host platform (subprocess: XLA_FLAGS must be set before
jax import, and the parent test process already initialised jax with one
device)."""
import os
import subprocess
import sys

import pytest


def _run(script, timeout=600):
    env = dict(os.environ, PYTHONPATH="src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=timeout)


_PRELUDE = r"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
import numpy as np
import jax
import jax.numpy as jnp
from repro.configs import get
from repro.launch.mesh import make_mesh
from repro.models.lm import init_params
from repro.serve import Request, ServeEngine, make_jit_steps
from repro.steps import greedy_oneshot, make_serve_step

assert jax.device_count() == 4, jax.devices()
N_REQ, PLEN, GEN_MAX, CACHE_LEN, PAGE = 6, 8, 6, 14, 7


def build(arch):
    cfg = get(arch).tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (N_REQ, PLEN), 0, cfg.vocab))
    return cfg, params, prompts


def reference(cfg, params, prompts, page):
    # single-device oneshot: no mesh, everything on the default device
    steps = make_jit_steps(cfg, cache_len=CACHE_LEN, page_size=page)
    serve_step = jax.jit(make_serve_step(cfg))
    return np.asarray(greedy_oneshot(
        steps["prefill"], serve_step, params, jnp.asarray(prompts),
        None, GEN_MAX))


def run_leg(cfg, params, prompts, ref, mesh, steps, seed, **kw):
    rng = np.random.default_rng(seed)
    gens = rng.integers(1, GEN_MAX + 1, N_REQ)
    order = rng.permutation(N_REQ)
    reqs = [Request(int(i), prompts[i], max_new_tokens=int(gens[i]))
            for i in order]
    with ServeEngine(cfg, params, slots=3, cache_len=CACHE_LEN,
                     mesh=mesh, umt=True, n_cores=4, jit_steps=steps,
                     **kw) as eng:
        assert eng.tp, "mesh with model>1 must enable tensor-parallel"
        for r in reqs:
            eng.submit(r)
        eng.close()
        eng.join()
    for r in reqs:
        assert r.done.is_set(), (kw, r.rid)
        got = np.asarray(r.out_tokens, np.int32)
        assert np.array_equal(got, ref[r.rid, :r.max_new]), (
            kw, r.rid, got.tolist(), ref[r.rid, :r.max_new].tolist())
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2.5-14b", "minicpm3-4b",
                                  "mamba2-780m"])
def test_tp_engine_bit_identical_to_single_device(arch):
    """Sharded engine greedy tokens == single-device one-shot rows, per
    request, across the policy grid on a (1, 4) mesh.  GQA shards KV
    heads, MLA replicates its latents, SSM shards state/conv channels
    (pure SSM pages no leaf: its per-slot rows ride the paged cache) —
    all three must come out bit-identical, not just close."""
    extra = ""
    if arch == "qwen2.5-14b":
        extra = r"""
# GQA also exercises the shard_map'd paged-attention kernel leg
steps = make_jit_steps(cfg, mesh, cache_len=CACHE_LEN, page_size=PAGE,
                       chunk=True, paged_kernel=True, tp=True)
run_leg(cfg, params, prompts, ref, mesh, steps, 7, page_size=PAGE,
        paged_kernel=True, policy="reserve")
"""
    body = (r"""
cfg, params, prompts = build("%s")
ref = reference(cfg, params, prompts, PAGE)
mesh = make_mesh((1, 4), ("data", "model"))
steps = make_jit_steps(cfg, mesh, cache_len=CACHE_LEN, page_size=PAGE,
                       tp=True)
for seed, policy in ((10, "reserve"), (11, "ondemand")):
    run_leg(cfg, params, prompts, ref, mesh, steps, seed, page_size=PAGE,
            policy=policy)
""" % arch) + extra + "\nprint(\"TP_GRID_OK\")\n"
    out = _run(_PRELUDE + body)
    assert "TP_GRID_OK" in out.stdout, (out.stdout[-1500:],
                                        out.stderr[-3000:])


@pytest.mark.slow
def test_tp_engine_collectives_footprint_and_donation():
    """Systems invariants of the sharded engine, asserted not eyeballed:
    the compiled decode HLO contains cross-device collectives (proof the
    partitioner actually split the math), every KV pool head-dim leaf
    holds exactly 1/4 of its bytes per device while the block table
    stays replicated, and a donated decode tick aliases every shard of
    the pool in place (per-shard buffer pointers survive)."""
    body = r"""
cfg, params, prompts = build("qwen2.5-14b")
mesh = make_mesh((1, 4), ("data", "model"))
with ServeEngine(cfg, params, slots=3, cache_len=CACHE_LEN, mesh=mesh,
                 umt=False, n_cores=4, page_size=PAGE) as eng:
    assert eng.tp and eng.stats()["tp"]
    kv = eng.kv

    # --- per-device footprint: sharded pool leaves hold 1/4 each
    n_sharded = 0
    for leaf in jax.tree.leaves(kv.cache):
        shards = leaf.addressable_shards
        assert len(shards) == 4, leaf.sharding
        per = shards[0].data.nbytes
        if per * 4 == leaf.nbytes:
            n_sharded += 1
        else:
            assert per == leaf.nbytes, (per, leaf.nbytes)  # replicated
    assert n_sharded >= 2, "k and v pools must shard on the head dim"
    assert kv.table_dev.sharding.is_fully_replicated
    print("BYTES_OK")

    # --- compiled decode carries cross-device collectives
    txt = eng.decode.lower(eng._params, kv.cache, eng._tokens,
                           eng._active_dev, kv.table_dev
                           ).compile().as_text()
    assert ("all-reduce" in txt or "all-gather" in txt or
            "reduce-scatter" in txt), txt[:2000]
    print("COLL_OK")

    # --- donation aliases every shard of the big pool leaf in place
    big = max(jax.tree.leaves(kv.cache), key=lambda x: x.nbytes)
    assert big.addressable_shards[0].data.nbytes * 4 == big.nbytes
    ptrs = {s.data.unsafe_buffer_pointer()
            for s in big.addressable_shards}
    toks, new_cache = eng.decode(eng._params, kv.cache, eng._tokens,
                                 eng._active_dev, kv.table_dev)
    jax.block_until_ready(toks)
    new_ptrs = set()
    for leaf in jax.tree.leaves(new_cache):
        for s in leaf.addressable_shards:
            new_ptrs.add(s.data.unsafe_buffer_pointer())
    assert ptrs <= new_ptrs, (
        "donated sharded decode did not alias the pool shards — "
        "out_shardings no longer match the committed input shardings")
    kv.commit(new_cache)
    print("ALIAS_OK")
print("TP_SYS_OK")
"""
    out = _run(_PRELUDE + body)
    for tag in ("BYTES_OK", "COLL_OK", "ALIAS_OK", "TP_SYS_OK"):
        assert tag in out.stdout, (tag, out.stdout[-1500:],
                                   out.stderr[-3000:])


@pytest.mark.slow
def test_tp_decode_carries_named_scopes():
    """The sharded decode step keeps its module name and every named
    scope after partitioning, the paged kernel's ``shard_map`` leg
    included, for latent (MLA) and grouped (GQA) attention."""
    body = r"""
import re
want = {"attn.proj", "attn.kv_write", "attn.kv_gather", "attn.core",
        "mlp", "embed", "head"}
mesh = make_mesh((1, 4), ("data", "model"))
for arch in ("minicpm3-4b", "qwen2.5-14b"):
    cfg, params, _ = build(arch)
    for kernel in (False, True):
        with ServeEngine(cfg, params, slots=3, cache_len=CACHE_LEN,
                         mesh=mesh, umt=False, n_cores=4, page_size=PAGE,
                         paged_kernel=kernel) as eng:
            kv = eng.kv
            txt = eng.decode.lower(eng._params, kv.cache, eng._tokens,
                                   eng._active_dev, kv.table_dev
                                   ).compile().as_text()
        assert txt.startswith("HloModule jit_decode_step,"), txt[:80]
        got = set()
        for name in set(re.findall(r'op_name="([^"]*)"', txt)):
            got.update(seg for seg in name.split("/") if seg in want)
        assert got == want - ({"attn.kv_gather"} if kernel else set()), (
            arch, kernel, sorted(got))
        print("SCOPES_OK", arch, kernel)
print("TP_SCOPES_OK")
"""
    out = _run(_PRELUDE + body)
    assert "TP_SCOPES_OK" in out.stdout, (out.stdout[-1500:],
                                          out.stderr[-3000:])


@pytest.mark.slow
def test_tp_prefill_kernel_branch_matches_xla_branch():
    """Tensor-parallel one-shot prefill on the TPU branch (interpret mode
    here): a ``pallas_call`` has no partitioning rule, so one flash
    kernel runs per head shard through ``shard_map``.  Its logits and
    caches match the single-device XLA branch, for GQA with two query
    heads per kv head on every shard and for MLA."""
    body = r"""
from repro.models import attention
from repro.steps import make_prefill_step

mesh = make_mesh((1, 4), ("data", "model"))
for arch, kw in (("qwen2.5-14b", dict(n_heads=8, n_kv_heads=4)),
                 ("minicpm3-4b", dict(v_head_dim=8))):
    cfg = get(arch).tiny(**kw)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0,
                                 cfg.vocab)
    want_cache, want = jax.jit(make_prefill_step(cfg, cache_len=48))(
        params, prompts, None)
    attention._backend = lambda: "tpu"
    step = make_prefill_step(cfg, mesh, cache_len=48, tp=True)
    jaxpr = str(jax.make_jaxpr(step)(params, prompts, None))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr, arch
    got_cache, got = jax.jit(step)(params, prompts, None)
    attention._backend = jax.default_backend
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    for g, w in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    print("PREFILL_OK", arch)
print("TP_PREFILL_OK")
"""
    out = _run(_PRELUDE + body)
    assert "TP_PREFILL_OK" in out.stdout, (out.stdout[-1500:],
                                           out.stderr[-3000:])
