"""Shared layers: norms, rope, MLPs, embeddings, losses (pure JAX)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding import shard


def scope(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    each op it emits carries ``name`` in its HLO ``op_name`` metadata and
    a device profile can attribute the op's time to it.  Metadata only:
    the compiled program is the same with or without it."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return scoped
    return wrap


def rms_norm(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def gated_rms_norm(x, z, w, eps=1e-5):
    """Mamba2-style norm: rmsnorm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                    w, eps)


# --------------------------------------------------------- cache invariance
def check_cache_invariant(old, new, where: str = "block"):
    """Trace-time guard for the donation contract: a cache-updating mode
    (decode / prefill_chunk) must return every cache leaf with exactly
    its input shape and dtype, or the serve engine's donated jits
    (``donate_argnums`` on the cache argument) could not alias the
    buffers in place and XLA would silently fall back to the full-pool
    copy donation exists to remove.  Costs nothing at runtime — it runs
    on tracers, once per compilation."""
    if old is None or new is None:
        return new
    tin, tout = jax.tree.structure(old), jax.tree.structure(new)
    assert tin == tout, (
        f"{where}: cache structure changed across update ({tin} -> {tout})")
    for i, o in zip(jax.tree.leaves(old), jax.tree.leaves(new)):
        assert i.shape == o.shape and i.dtype == o.dtype, (
            f"{where}: cache leaf {i.shape}/{i.dtype} -> "
            f"{o.shape}/{o.dtype} breaks the donation (aliasing) contract")
    return new


# ------------------------------------------------------------------- paging
@scope("attn.kv_gather")
def page_gather(pool, table, page_size):
    """Materialise a slot-major dense view of a paged KV pool.

    pool: (P, page_size, ...) physical pages; table: (B, pages_per_slot)
    int32 physical page ids (page 0 is the reserved garbage page, so
    unallocated logical pages gather zeros-or-garbage that the position
    mask must cover).  Returns (B, pages_per_slot * page_size, ...) — the
    same dense layout a per-slot cache row would have, so the attention
    math downstream is untouched (and bit-identical) relative to the
    unpaged cache.

    This materialisation is the copy the fused paged-attention kernel
    eliminates: with ``pages["kernel"]`` set, decode attention walks the
    block table inside repro.kernels.paged_attention and never calls
    this — it stays as the default A/B leg and the oracle."""
    b, pps = table.shape
    gathered = pool[table]                     # (B, pps, page_size, ...)
    return gathered.reshape((b, pps * page_size) + pool.shape[2:])


@scope("attn.kv_write")
def page_scatter(pool, table, page_size, idx, update):
    """Write one token row per slot into the paged pool.

    idx: (B,) per-slot logical positions; update: (B, 1, ...) decode-step
    values.  The logical position maps through the slot's block table to
    (physical page, offset).  Slots whose table entry is the garbage page
    (dead slots, frozen ``idx``) all collide on page 0 — harmless, it is
    never gathered into a valid (masked-in) position."""
    page = jnp.take_along_axis(table, (idx // page_size)[:, None],
                               axis=1)[:, 0]                     # (B,)
    return pool.at[page, idx % page_size].set(
        update[:, 0].astype(pool.dtype))


@scope("attn.kv_write")
def page_scatter_window(pool, table, page_size, pos, update, n_tok):
    """Write a per-slot multi-token window into the paged pool (the
    speculative-decode verify append).

    pos: (B,) first logical position per slot; update: (B, S, ...) the
    verify window's values; n_tok: (B,) valid window lengths (0 for dead
    slots).  Lane j of slot b lands at logical position ``pos_b + j``
    when ``j < n_tok_b``; masked lanes — padding past a short draft, and
    every lane of a dead slot — are redirected to garbage page 0 (the
    same convention dead slots already use in :func:`page_scatter`), so
    a padded write can never touch a live page.  Valid lanes of
    distinct slots never collide: each slot owns its pages."""
    b, s = update.shape[:2]
    idx = pos[:, None] + jnp.arange(s)                        # (B, S)
    valid = jnp.arange(s)[None, :] < n_tok[:, None]           # (B, S)
    # clip protects masked lanes whose logical page would run off the
    # table; valid lanes are always covered (the engine grows/reserves
    # pages through pos + n_tok - 1 before dispatch)
    lp = jnp.clip(idx // page_size, 0, table.shape[1] - 1)
    page = jnp.where(valid, jnp.take_along_axis(table, lp, axis=1), 0)
    off = jnp.where(valid, idx % page_size, 0)
    return pool.at[page, off].set(update.astype(pool.dtype))


# ----------------------------------------------------------------- positions
def rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))


@scope("attn.proj")
def apply_rope(x, pos, theta=10_000.0):
    """x: (..., S, H, Dh) or (..., S, Dh); pos: scalar, (S,), or (B, S)
    (per-slot decode positions, continuous batching) — broadcast over x."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta))           # (dh/2,)
    angles = jnp.asarray(pos, jnp.float32)[..., None] * freqs  # (..., S, dh/2)
    if x.ndim == 4 and angles.ndim >= 2:                 # heads dim present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(pos, dim: int):
    """(S,) -> (S, dim) classic transformer sinusoidal embedding."""
    half = dim // 2
    freqs = jnp.exp(-np.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------- MLP
def mlp_dense(x, p, cfg):
    """SwiGLU MLP. x: (B, S, D)."""
    del cfg
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    h = shard(jax.nn.silu(h) * u, "batch", "seq", "ff_act")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))


def embed_tokens(tokens, p_embed, cfg, dtype):
    """tokens: (B, S) int32 or (B, S, K) for audio codebooks."""
    dt = jnp.dtype(dtype)
    if cfg.frontend == "audio_codebooks":
        # sum of K codebook embeddings (MusicGen-style)
        emb = p_embed["tok"].astype(dt)                  # (K, V, D)
        out = 0.0
        for k in range(cfg.n_codebooks):
            out = out + jnp.take(emb[k], tokens[..., k], axis=0)
        return out
    emb = p_embed["tok"].astype(dt)                      # (V, D)
    return jnp.take(emb, tokens, axis=0)


def lm_logits(x, params, cfg):
    """x: (B, S, D) -> logits.  Audio: (B, S, K, V); else (B, S, V)."""
    dt = x.dtype
    if cfg.frontend == "audio_codebooks":
        w = params["lm_head"].astype(dt)                 # (K, D, V)
        return jnp.einsum("bsd,kdv->bskv", x, w)
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(dt).T          # (D, V)
    else:
        w = params["lm_head"].astype(dt)                 # (D, V)
    return shard(jnp.einsum("bsd,dv->bsv", x, w), "batch", "seq", "vocab")


def softmax_xent(logits, labels, z_loss=0.0):
    """Stable CE in f32 over (possibly sharded) vocab; labels: int32 ids.

    Returns per-token loss with the z-loss regulariser folded in.
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + jnp.squeeze(m, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - gold
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return loss
