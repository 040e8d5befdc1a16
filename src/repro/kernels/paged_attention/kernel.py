"""Fused paged decode attention for TPU (vLLM-PagedAttention style).

One query token per slot attends over its K/V **pages in place**: the
per-slot block table rides in as a scalar-prefetch argument, so the K/V
BlockSpec index maps resolve the *physical* page id ``table[slot, p]``
while the grid walks *logical* pages — the dense slot-major copy the
unfused path materialises every tick (``page_gather``) never exists.
``page_size`` is the kv tile parameter: the online-softmax running
statistics (m, l, acc) live in VMEM scratch and carry across the
sequential last grid dim, exactly as in ``flash_attention``.

Grid (GQA): (slots, pages_per_slot).  Each program reads one page of
every kv head — block ``(1, page_size, Hkv, Dh)``, whose last two dims
are the pool's own, as Mosaic's tiling rule requires — and loops the kv
heads inside, scoring each head's query group (``group = n_heads //
n_kv_heads`` rows, the GQA head-group mapping folded into the q/out
BlockSpecs) against that head's (page_size, head_dim) slice; per-head
running statistics sit at their own leading scratch index.  Masking is
per-slot: logical position
``p * page_size + j`` is valid iff ``<= pos[slot]``, and additionally
``> pos[slot] - window`` when a sliding window is set (vacuous for the
degenerate-linear rings the engine pages — window >= cache_len — but
supported for generality).  Pages entirely outside the valid range skip
their matmuls via ``pl.when``.  Dead slots (block-table rows pointing at
garbage page 0) read the garbage page exactly like the gather path
does, so both legs see identical values; rows with no valid position
emit zeros via the ``l == 0`` guard.

Grid (MLA absorbed): (slots, pages_per_slot) with one latent "kv head"
shared by every query head; scores are the sum of the latent and rope
dot products and the accumulator contracts probabilities against the
latent page itself — the absorbed form's V *is* its K, so a single pair
of page reads feeds both sides.

VMEM working set per program is one page of every kv head + the query
heads (~page_size x Hkv x head_dim + H x head_dim floats) — tiny against
the ~16 MB budget; small pages under-fill the (8, 128) f32 tile and are
padded by Mosaic, which is the price of page_size as a tile parameter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_update(s, mask, v, m_scr, l_scr, acc_scr):
    """One online-softmax step over scores ``s`` (rows, cols) against
    values ``v`` (cols, d), masked by ``mask``; updates the running
    stats, kept 2-D — (rows, 1) max and sum, (rows, d) accumulator."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * alpha +
                    jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    m_scr[...] = m_new


def _finalize(l_scr, acc_scr):
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)     # fully-masked rows -> zeros
    return acc_scr[...] / l


def _decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, window, page_size,
                   n_pages):
    s = pl.program_id(0)
    pi = pl.program_id(1)
    hkv = q_ref.shape[1]

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[s]
    # pages entirely above the slot's position (or below its window)
    # contribute nothing — skip their matmuls
    relevant = pi * page_size <= pos
    if window is not None:
        relevant &= (pi + 1) * page_size - 1 > pos - window

    @pl.when(relevant)
    def _body():
        group = q_ref.shape[2]
        kpos = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (group, page_size), 1)
        mask = kpos <= pos
        if window is not None:
            mask &= kpos > pos - window
        # one page block holds every kv head: score each head's query
        # group against its own (page_size, head_dim) slice
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)             # (group, dh)
            k = k_ref[0, :, h, :].astype(jnp.float32)       # (ps, dh)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (group, ps)
            _online_update(sc, mask, v, m_scr.at[h], l_scr.at[h],
                           acc_scr.at[h])

    @pl.when(pi == n_pages - 1)
    def _final():
        for h in range(hkv):
            o_ref[0, h] = _finalize(l_scr.at[h],
                                    acc_scr.at[h]).astype(o_ref.dtype)


def paged_decode_attention_pools(q, k_pool, v_pool, table, pos, *,
                                 page_size, window=None, scale=None,
                                 interpret=False):
    """q: (B, Hkv, group, Dh) — head h of the flat layout is row
    (h // group, h % group); k/v pools: (P, page_size, Hkv, Dh);
    table: (B, pages_per_slot) int32; pos: (B,) int32."""
    b, hkv, group, dh = q.shape
    n_pages = table.shape[1]
    assert k_pool.shape[1] == page_size, (k_pool.shape, page_size)
    scale = (dh ** -0.5) if scale is None else scale

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, page_size=page_size,
        n_pages=n_pages)

    # the page block spans every kv head, so its last two dims (Hkv, Dh)
    # are the pool's own — the tiling rule Mosaic enforces holds for any
    # head count, including a tensor-parallel shard's local heads
    page_spec = pl.BlockSpec((1, page_size, hkv, dh),
                             lambda s, p, t_, p_: (t_[s, p], 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages),
            in_specs=[
                pl.BlockSpec((1, hkv, group, dh),
                             lambda s, p, t_, p_: (s, 0, 0, 0)),
                # page indirection: physical page = table[slot, page]
                page_spec,
                page_spec,
            ],
            out_specs=pl.BlockSpec((1, hkv, group, dh),
                                   lambda s, p, t_, p_: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hkv, group, 1), jnp.float32),
                pltpu.VMEM((hkv, group, 1), jnp.float32),
                pltpu.VMEM((hkv, group, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dh), q.dtype),
        interpret=interpret,
    )(table, pos, q, k_pool, v_pool)


def _mla_kernel(table_ref, pos_ref, ql_ref, qr_ref, c_ref, r_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale, page_size, n_pages):
    s = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[s]

    @pl.when(pi * page_size <= pos)
    def _body():
        ql = ql_ref[0, 0].astype(jnp.float32)           # (H, rkv)
        qr = qr_ref[0, 0].astype(jnp.float32)           # (H, dr)
        c = c_ref[0].astype(jnp.float32)                # (ps, rkv)
        r = r_ref[0].astype(jnp.float32)                # (ps, dr)
        sc = (jax.lax.dot_general(
                  ql, c, (((1,), (1,)), ((), ())),
                  preferred_element_type=jnp.float32) +
              jax.lax.dot_general(
                  qr, r, (((1,), (1,)), ((), ())),
                  preferred_element_type=jnp.float32)) * scale  # (H, ps)
        kpos = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        mask = kpos <= pos
        # the absorbed form's V is the latent page itself
        _online_update(sc, mask, c, m_scr, l_scr, acc_scr)

    @pl.when(pi == n_pages - 1)
    def _final():
        o_ref[0, 0] = _finalize(l_scr, acc_scr).astype(o_ref.dtype)


def paged_mla_decode_attention_pools(q_lat, q_rope, ckv_pool, krope_pool,
                                     table, pos, *, page_size, scale,
                                     interpret=False):
    """q_lat: (B, 1, H, Rkv) absorbed queries; q_rope: (B, 1, H, Dr);
    pools: (P, page_size, Rkv) / (P, page_size, Dr); table: (B, pps)
    int32; pos: (B,) int32.  Returns the attended latent (B, 1, H, Rkv)
    — the caller applies wv_b outside."""
    b, _, h, rkv = q_lat.shape
    n_pages = table.shape[1]
    assert ckv_pool.shape[1] == page_size, (ckv_pool.shape, page_size)

    kernel = functools.partial(
        _mla_kernel, scale=scale, page_size=page_size, n_pages=n_pages)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages),
            in_specs=[
                pl.BlockSpec((1, 1, h, rkv),
                             lambda s, p, t_, p_: (s, 0, 0, 0)),
                pl.BlockSpec((1, 1, h, q_rope.shape[-1]),
                             lambda s, p, t_, p_: (s, 0, 0, 0)),
                pl.BlockSpec((1, page_size, rkv),
                             lambda s, p, t_, p_: (t_[s, p], 0, 0)),
                pl.BlockSpec((1, page_size, krope_pool.shape[-1]),
                             lambda s, p, t_, p_: (t_[s, p], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, h, rkv),
                                   lambda s, p, t_, p_: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rkv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, rkv), q_lat.dtype),
        interpret=interpret,
    )(table, pos, q_lat, q_rope, ckv_pool, krope_pool)
