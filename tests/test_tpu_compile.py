"""The serve path's Pallas kernels compile for a TPU v5e — checked here,
without a chip, against a described ``v5e:2x2`` topology.

Mosaic refuses what the interpreter accepts (block shapes off the
(8, 128) tiling, VMEM overruns), so every other kernel test, which runs
in interpret mode on the CPU, cannot catch it.  These compile the paged
decode kernels at the widths the chip serves: GQA at qwen2.5-14b's
(8 kv heads, group 5, head_dim 128) and at one tensor-parallel shard's
(2 kv heads), and absorbed MLA at minicpm3-4b's (40 heads, latent 256,
rope 32) — bf16 pools, page_size 16.  The prefill flash kernel compiles
at minicpm3-4b's prefill (40 heads, q/k 96, v 64, 5,120 tokens), at a
ragged chat length, and at one qwen2.5-14b tensor-parallel shard's.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.paged_attention import ops

SLOTS, PAGE, PAGES_PER_SLOT = 8, 16, 32
POOL_PAGES = SLOTS * PAGES_PER_SLOT + 1       # + the garbage page


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off while it is in use (an entry compiled for a described chip
    cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — any failure
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _gqa(heads, kv_heads):
    q = (SLOTS, 1, heads, 128)
    pool = (POOL_PAGES, PAGE, kv_heads, 128)
    return (ops.paged_decode_attention, dict(page_size=PAGE),
            [(q, jnp.bfloat16), (pool, jnp.bfloat16), (pool, jnp.bfloat16)])


def _mla(heads, rank, rope):
    return (ops.paged_mla_decode_attention,
            dict(page_size=PAGE, scale=96 ** -0.5),
            [((SLOTS, 1, heads, rank), jnp.bfloat16),
             ((SLOTS, 1, heads, rope), jnp.bfloat16),
             ((POOL_PAGES, PAGE, rank), jnp.bfloat16),
             ((POOL_PAGES, PAGE, rope), jnp.bfloat16)])


KERNELS = {
    "gqa-qwen2.5-14b": _gqa(40, 8),
    "gqa-qwen2.5-14b-tp-shard": _gqa(10, 2),
    "mla-minicpm3-4b": _mla(40, 256, 32),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_paged_kernel_compiles_for_v5e(name, one_chip):
    fn, static, arrays = KERNELS[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in arrays]
    shapes += [jax.ShapeDtypeStruct((SLOTS, PAGES_PER_SLOT), jnp.int32,
                                    sharding=one_chip),
               jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)]
    compiled = jax.jit(
        lambda *a: fn(*a, interpret=False, **static)).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (seq, heads, kv heads, q/k width, v width, window)
FLASH = {
    "mla-minicpm3-4b-5120": (5120, 40, 40, 96, 64, None),
    "mla-minicpm3-4b-ragged": (1000, 40, 40, 96, 64, None),
    "gqa-qwen2.5-14b-tp-shard": (2048, 10, 2, 128, 128, None),
    "gqa-window": (3000, 32, 8, 128, 128, 1024),
}


@pytest.mark.parametrize("name", sorted(FLASH))
def test_flash_kernel_compiles_for_v5e(name, one_chip):
    s, h, hkv, d, dv, window = FLASH[name]
    shapes = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
              for shape in ((1, s, h, d), (1, s, hkv, d), (1, s, hkv, dv))]
    compiled = jax.jit(lambda q, k, v: flash_ops.flash_attention(
        q, k, v, window=window, interpret=False)).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
