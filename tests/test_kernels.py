"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (flash_attention, flash_attention_ref,
                           paged_decode_attention,
                           paged_decode_attention_ref,
                           paged_mla_decode_attention,
                           paged_mla_decode_attention_ref, rms_norm,
                           rms_norm_ref, ssd_scan, ssd_scan_ref)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _close(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **_tol(dtype))


# ------------------------------------------------------------------ flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,h,hkv,d", [
    (1, 128, 128, 2, 2, 64),      # MHA square
    (2, 256, 256, 4, 1, 64),      # MQA
    (1, 128, 256, 8, 2, 128),     # GQA, cross lengths
    (1, 64, 64, 2, 2, 32),        # small head_dim
])
def test_flash_attention_matches_ref(b, sq, sk, h, hkv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    want = flash_attention_ref(q, k, v, causal=True)
    _close(out, want, dtype)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, block_q=32,
                          block_k=32, interpret=True)
    want = flash_attention_ref(q, k, v, causal=True, window=window)
    _close(out, want, jnp.float32)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 64, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=64,
                          interpret=True)
    want = flash_attention_ref(q, k, v, causal=False)
    _close(out, want, jnp.float32)


def test_flash_attention_block_shape_invariance():
    """Output must not depend on the chosen BlockSpec tiling."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    outs = [flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True)
            for bq, bk in ((32, 32), (64, 128), (256, 64))]
    for o in outs[1:]:
        _close(o, outs[0], jnp.float32)


# Prefill shapes: (b, s, h, hkv, d, dv, window, blocks, heads per step);
# blocks None -> ops.block_sizes chooses (one block at these lengths)
PREFILL_CASES = {
    "mla-40h": (1, 192, 40, 40, 96, 64, None, 64, 8),
    "mla-40h-chosen-ragged": (1, 200, 40, 40, 96, 64, None, None, None),
    "gqa-heads-in-group": (2, 256, 8, 2, 64, 64, None, 64, 2),
    "gqa-groups-in-step": (1, 256, 8, 2, 64, 64, None, 64, 8),
    "gqa-window": (1, 256, 4, 2, 32, 32, 40, 64, 2),
    "ragged": (1, 100, 4, 4, 96, 64, None, 32, 4),
    "ragged-window-gqa": (1, 130, 6, 2, 64, 32, 50, 32, 3),
}


def _prefill_inputs(case, dtype):
    b, s, h, hkv, d, dv, window, blocks, heads = PREFILL_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dv), dtype)
    return q, k, v, dict(window=window, block_q=blocks, block_k=blocks,
                         heads=heads)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(PREFILL_CASES))
def test_flash_attention_prefill_shapes(case, dtype):
    """Separate value width (MLA: q/k 96, v 64), GQA with heads per step
    inside one group or spanning groups, sliding windows and lengths off
    the block (zero-padded and masked): the kernel matches the oracle and
    the XLA prefill path it replaces on the TPU."""
    from repro.models.attention import qchunk_attention

    q, k, v, kw = _prefill_inputs(case, dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True, **kw)
    assert out.shape == q.shape[:3] + v.shape[-1:]
    assert np.isfinite(np.asarray(out, np.float32)).all()
    _close(out, flash_attention_ref(q, k, v, causal=True,
                                    window=kw["window"]), dtype)
    _close(out, qchunk_attention(q, k, v, window=kw["window"]), dtype)


@pytest.mark.parametrize("case", ["gqa-window", "ragged-window-gqa",
                                  "mla-40h"])
def test_flash_attention_clamped_kv_index_map(case):
    """Clamping the K/V index map to the blocks the mask reaches changes
    which block is resident during skipped steps, never the result."""
    q, k, v, kw = _prefill_inputs(case, jnp.float32)
    clamped = flash_attention(q, k, v, causal=True, interpret=True, **kw)
    plain = flash_attention(q, k, v, causal=True, clamp_kv=False,
                            interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(clamped), np.asarray(plain))


@pytest.mark.parametrize("causal,sq,sk", [(False, 50, 100), (True, 128, 100)])
def test_flash_attention_padded_keys_are_masked(causal, sq, sk):
    """Keys zero-padded to a whole block are masked wherever a real query
    row could reach them: without a causal mask, or when there are more
    queries than keys."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, sq, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    _close(out, flash_attention_ref(q, k, v, causal=causal), jnp.float32)


def test_flash_block_sizes_follow_the_shape():
    from repro.kernels.flash_attention.ops import block_sizes

    assert block_sizes(5120, 40, 1) == (512, 8)       # minicpm3-4b prefill
    assert block_sizes(37, 40, 1) == (128, 20)        # short chat prompt
    assert block_sizes(5120, 40, 5) == (512, 5)       # qwen2.5-14b, group 5
    assert block_sizes(5120, 10, 5) == (512, 5)       # its 4-way shard
    for seq, h, g in ((300, 6, 3), (9000, 7, 7), (1, 1, 1)):
        blk, hb = block_sizes(seq, h, g)
        assert blk % 128 == 0 and h % hb == 0
        assert g % hb == 0 or hb % g == 0


# ------------------------------------------------------------------ paged
def _paged_table(b, cache_len, ps, pos, garbage_rest=True):
    """Per-slot block table covering each slot's ``pos``; every entry
    past the covered extent stays on the garbage page 0 (the engine's
    convention for unallocated pages)."""
    pps = cache_len // ps
    table = np.zeros((b, pps), np.int32)
    nxt = 1
    for i in range(b):
        for p in range(-(-(int(pos[i]) + 1) // ps)):
            table[i, p] = nxt
            nxt += 1
        if not garbage_rest:
            for p in range(-(-(int(pos[i]) + 1) // ps), pps):
                table[i, p] = nxt
                nxt += 1
    return jnp.asarray(table), 1 + b * pps


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,dh,cache_len,ps", [
    (2, 4, 4, 32, 16, 4),         # MHA
    (3, 8, 2, 64, 32, 8),         # GQA group 4
    (2, 4, 1, 32, 16, 2),         # MQA
    (2, 6, 3, 16, 12, 1),         # page_size 1 (one token per page)
    (1, 2, 2, 32, 8, 8),          # single page covers the cache
])
def test_paged_decode_matches_ref(b, h, hkv, dh, cache_len, ps, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    pos = np.array(jax.random.randint(ks[3], (b,), 0, cache_len))
    pos[0] = cache_len - 1            # full slot rides every grid page
    table, num_pages = _paged_table(b, cache_len, ps, pos)
    q = jax.random.normal(ks[0], (b, 1, h, dh), dtype)
    kp = jax.random.normal(ks[1], (num_pages, ps, hkv, dh), dtype)
    vp = jax.random.normal(ks[2], (num_pages, ps, hkv, dh), dtype)
    out = paged_decode_attention(q, kp, vp, table, jnp.asarray(pos),
                                 page_size=ps, interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, table, jnp.asarray(pos),
                                      page_size=ps)
    assert out.shape == (b, 1, h, dh)
    _close(out, want, dtype)


@pytest.mark.parametrize("window", [1, 3, 7, 100])
def test_paged_decode_sliding_window(window):
    b, h, hkv, dh, cache_len, ps = 3, 4, 2, 32, 24, 4
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    pos = np.array(jax.random.randint(ks[3], (b,), 0, cache_len))
    table, num_pages = _paged_table(b, cache_len, ps, pos)
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, ps, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, ps, hkv, dh), jnp.float32)
    out = paged_decode_attention(q, kp, vp, table, jnp.asarray(pos),
                                 page_size=ps, window=window,
                                 interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, table, jnp.asarray(pos),
                                      page_size=ps, window=window)
    _close(out, want, jnp.float32)


@pytest.mark.parametrize("b,h,rkv,dr,cache_len,ps", [
    (2, 4, 32, 16, 16, 4),
    (3, 2, 16, 8, 12, 1),         # page_size 1
    (1, 8, 64, 32, 8, 8),         # single page
])
def test_paged_mla_decode_matches_ref(b, h, rkv, dr, cache_len, ps):
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    pos = np.array(jax.random.randint(ks[4], (b,), 0, cache_len))
    pos[-1] = cache_len - 1
    table, num_pages = _paged_table(b, cache_len, ps, pos)
    q_lat = jax.random.normal(ks[0], (b, 1, h, rkv), jnp.float32)
    q_rope = jax.random.normal(ks[1], (b, 1, h, dr), jnp.float32)
    ckv = jax.random.normal(ks[2], (num_pages, ps, rkv), jnp.float32)
    krope = jax.random.normal(ks[3], (num_pages, ps, dr), jnp.float32)
    scale = (rkv + dr) ** -0.5
    out = paged_mla_decode_attention(q_lat, q_rope, ckv, krope, table,
                                     jnp.asarray(pos), page_size=ps,
                                     scale=scale, interpret=True)
    want = paged_mla_decode_attention_ref(q_lat, q_rope, ckv, krope,
                                          table, jnp.asarray(pos),
                                          page_size=ps, scale=scale)
    assert out.shape == (b, 1, h, rkv)
    _close(out, want, jnp.float32)


def test_paged_decode_garbage_page_is_inert():
    """Unallocated table entries point at page 0; whatever it holds
    (here: huge values) must never leak into any slot's output —
    the in-kernel walk masks by ``pos`` exactly like the gather leg."""
    b, h, hkv, dh, cache_len, ps = 3, 4, 2, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    pos = np.asarray([0, 5, cache_len - 1])   # ragged, incl. both edges
    table, num_pages = _paged_table(b, cache_len, ps, pos)
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, ps, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, ps, hkv, dh), jnp.float32)
    poisoned = (kp.at[0].set(1e4), vp.at[0].set(1e4))
    out = paged_decode_attention(q, *poisoned, table, jnp.asarray(pos),
                                 page_size=ps, interpret=True)
    clean = paged_decode_attention(q, kp, vp, table, jnp.asarray(pos),
                                   page_size=ps, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    assert np.isfinite(np.asarray(out)).all()


def test_paged_decode_allocated_but_future_pages_masked():
    """Tables where *allocated* pages extend past ``pos`` (the engine
    allocates a page before the tick that first writes it): positions
    beyond ``pos`` must still be masked out."""
    b, h, hkv, dh, cache_len, ps = 2, 2, 2, 16, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    pos = np.asarray([2, 9])
    table, num_pages = _paged_table(b, cache_len, ps, pos,
                                    garbage_rest=False)
    q = jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, ps, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, ps, hkv, dh), jnp.float32)
    out = paged_decode_attention(q, kp, vp, table, jnp.asarray(pos),
                                 page_size=ps, interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, table, jnp.asarray(pos),
                                      page_size=ps)
    _close(out, want, jnp.float32)


# -------------------------------------------------------------------- ssd
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 128, 2, 64, 32, 32),
    (2, 256, 4, 32, 64, 64),
    (1, 64, 1, 16, 16, 64),       # single chunk
])
def test_ssd_scan_matches_ref(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bmat = jax.random.normal(ks[3], (b, s, h, n), dtype) * 0.5
    cmat = jax.random.normal(ks[0], (b, s, h, n), dtype) * 0.5
    y, hf = ssd_scan(x, dt, a, bmat, cmat, chunk=chunk, interpret=True)
    y_ref, hf_ref = ssd_scan_ref(x, dt, a, bmat, cmat, chunk=chunk)
    tol = dict(rtol=4e-2, atol=4e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(hf, np.float32),
                               np.asarray(hf_ref, np.float32), **tol)


def test_ssd_scan_state_carries_across_chunks():
    """Same input, different chunk sizes -> same output (the recurrence
    must be chunk-size invariant)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    b, s, h, p, n = 1, 128, 2, 32, 32
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bmat = jax.random.normal(ks[3], (b, s, h, n), jnp.float32) * 0.5
    cmat = jax.random.normal(ks[0], (b, s, h, n), jnp.float32) * 0.5
    outs = [ssd_scan(x, dt, a, bmat, cmat, chunk=c, interpret=True)[0]
            for c in (16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(128, 256), (4, 32, 512), (1, 64)])
def test_rms_norm_matches_ref(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], shape, dtype)
    w = jax.random.normal(ks[1], (shape[-1],), jnp.float32) * 0.1 + 1.0
    out = rms_norm(x, w, interpret=True)
    want = rms_norm_ref(x, w)
    _close(out, want, dtype)
