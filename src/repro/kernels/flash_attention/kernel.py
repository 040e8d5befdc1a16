"""Tiled flash attention (online softmax) for TPU.

Grid: (query-head blocks, q blocks, kv blocks).  The last grid dim is
sequential on TPU, so the running statistics (m, l, acc) of every head
in the block live in VMEM scratch and carry across kv blocks.  One step
runs ``heads`` query heads in a loop, so a long prompt needs few grid
steps and each one does enough matrix work to hide the step's fixed
cost.  GQA is folded into the K/V BlockSpec index maps (q-head ``h``
reads kv-head ``h // group``), so the expanded K/V never exists.

Scores never leave VMEM: a (block_q, block_k) f32 tile is formed, masked,
exponentiated and multiplied into the accumulator in place.  Operands go
into both matrix products in their own dtype (bf16 on the serve path)
with f32 accumulation; the softmax statistics are f32 and the
probabilities enter the second product in the operands' dtype.  The
value width may differ from the query/key width (MLA: 96 and 64).

Masking: causal (query ``i`` sees keys ``<= i``), an optional sliding
window (keys ``> i - window``) and the padded key tail (``>= kv_len``).
Blocks that the mask leaves empty skip their work via ``pl.when``, and
their K/V index maps are clamped to the nearest block that is used, so
Pallas re-uses the resident block instead of fetching one it would skip.
Only blocks the mask cuts through pay for the elementwise mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# v5e has 128 MiB of VMEM; the scoped default (16 MiB) is below what a
# step of several heads at 512-row blocks double-buffers
VMEM_LIMIT = 96 * 1024 * 1024


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, kv_len, block_q, block_k, n_k):
    heads, kv_heads = q_ref.shape[0], k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_first, k_first = qi * block_q, ki * block_k
    q_last, k_last = q_first + block_q - 1, k_first + block_k - 1
    run = True            # some (query, key) pair of the block is seen
    edge = False          # the mask cuts through the block
    if causal:
        run = k_first <= q_last
        edge = k_last > q_first
    if window is not None:
        run = run & (k_last > q_first - window)
        edge = edge | (k_first <= q_last - window)
    if kv_len % block_k:
        edge = edge | (k_last >= kv_len)

    def attend(masked):
        mask = None
        if masked:
            shape = (block_q, block_k)
            qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = kpos < kv_len
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window

        def head(j, carry):
            kj = j * kv_heads // heads
            s = jax.lax.dot_general(
                q_ref[j], k_ref[kj], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(mask, p, 0.0)
            l_scr[j] = alpha * l_scr[j] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[j] = alpha * acc_scr[j] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[kj], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[j] = m_new
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    if edge is False:
        pl.when(run)(lambda: attend(False))
    else:
        pl.when(run & edge)(lambda: attend(True))
        pl.when(run & jnp.logical_not(edge))(lambda: attend(False))

    @pl.when(ki == n_k - 1)
    def _final():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)     # rows that saw no key -> zeros
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _kv_block(b, qi, ki, *, heads, group, kv_heads, block_q, block_k,
              causal, window, n_k, clamp):
    """K/V block index of grid step (b, qi, ki): kv-head block of the
    query-head block ``b``, and the kv block ``ki`` clamped into the
    range the q block's mask reaches (``clamp``)."""
    if clamp:
        hi = ((qi + 1) * block_q - 1) // block_k if causal else n_k - 1
        ki = jnp.minimum(ki, jnp.minimum(hi, n_k - 1))
        if window is not None:
            lo = jnp.maximum(qi * block_q - window + 1, 0) // block_k
            ki = jnp.maximum(ki, lo)
    return b * heads // group // kv_heads, ki, 0


def flash_attention_bhsd(q, k, v, *, causal=True, window=None, scale=None,
                         block_q=256, block_k=256, heads=1, kv_len=None,
                         clamp_kv=True, interpret=False):
    """q: (BH, Sq, D); k: (BHkv, Sk, D); v: (BHkv, Sk, Dv) with
    BH % BHkv == 0 -> (BH, Sq, Dv).

    Sq and Sk must be multiples of ``block_q`` and ``block_k``: a caller
    with ragged lengths zero-pads them and passes the true key count as
    ``kv_len``, which masks the padded keys.  ``heads`` query heads run
    per grid step; with ``group = BH // BHkv`` either ``heads`` divides
    ``group`` or ``group`` divides ``heads``, so a step's query heads
    read whole kv heads.  ``clamp_kv=False`` keeps the plain index map
    that fetches every K/V block (the reference the clamp is tested
    against)."""
    bh, sq, d = q.shape
    bhk, sk, _ = k.shape
    dv = v.shape[-1]
    group = bh // bhk
    assert bh == group * bhk, (bh, bhk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q,
                                                     block_k)
    assert bh % heads == 0 and (group % heads == 0 or heads % group == 0), (
        bh, heads, group)
    scale = (d ** -0.5) if scale is None else scale
    kv_len = sk if kv_len is None else kv_len
    kv_heads = max(1, heads // group)
    n_q, n_k = sq // block_q, sk // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, kv_len=kv_len,
        block_q=block_q, block_k=block_k, n_k=n_k)
    kv_map = functools.partial(
        _kv_block, heads=heads, group=group, kv_heads=kv_heads,
        block_q=block_q, block_k=block_k, causal=causal, window=window,
        n_k=n_k, clamp=clamp_kv)

    return pl.pallas_call(
        kernel,
        grid=(bh // heads, n_q, n_k),
        in_specs=[
            pl.BlockSpec((heads, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((kv_heads, block_k, d), kv_map),
            pl.BlockSpec((kv_heads, block_k, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((heads, block_q, dv),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((heads, block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v)
