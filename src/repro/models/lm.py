"""Unified decoder LM over a repeating block pattern, lowered as
``lax.scan`` over pattern repeats (HLO size is O(pattern), not O(layers)).

Params / caches are described by a single *meta* tree (shape, logical axes,
init kind); init, ShapeDtypeStructs and shardings all derive from it.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding import shard
from .blocks import (block_apply, block_cache_shapes, block_param_shapes,
                     paged_names)
from .layers import embed_tokens, lm_logits, rms_norm, sinusoidal_pos


class LeafMeta(NamedTuple):
    shape: tuple
    axes: tuple
    init: str = "normal"


def _is_meta_src(x):
    return isinstance(x, tuple) and len(x) in (2, 3) and isinstance(x[0],
                                                                    tuple)


def _to_meta(tree):
    return jax.tree_util.tree_map(
        lambda t: LeafMeta(*t), tree, is_leaf=_is_meta_src)


def _stack_meta(meta, repeats):
    return jax.tree_util.tree_map(
        lambda m: LeafMeta((repeats,) + m.shape, ("stack",) + m.axes, m.init),
        meta, is_leaf=lambda x: isinstance(x, LeafMeta))


# ------------------------------------------------------------------- meta
def param_meta(cfg):
    d, v = cfg.d_model, cfg.vocab
    if cfg.frontend == "audio_codebooks":
        embed = {"tok": LeafMeta((cfg.n_codebooks, v, d),
                                 (None, "vocab", "fsdp"))}
        head = LeafMeta((cfg.n_codebooks, d, v), (None, "fsdp", "vocab"))
    else:
        embed = {"tok": LeafMeta((v, d), ("vocab", "fsdp"))}
        head = LeafMeta((d, v), ("fsdp", "vocab"))
    blocks = tuple(
        _stack_meta(_to_meta(block_param_shapes(cfg, spec)), cfg.n_repeats)
        for spec in cfg.pattern)
    out = {
        "embed": embed,
        "blocks": blocks,
        "final_norm": LeafMeta((d,), (None,), "ones"),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = head
    return out


def cache_meta(cfg, batch: int, seq: int):
    blocks = tuple(
        _stack_meta(_to_meta(block_cache_shapes(cfg, spec, batch, seq)),
                    cfg.n_repeats)
        for spec in cfg.pattern)
    return {"pos": LeafMeta((), (), "zeros"), "blocks": blocks}


def _meta_leaves(tree):
    return jax.tree_util.tree_map(lambda m: m, tree,
                                  is_leaf=lambda x: isinstance(x, LeafMeta))


def meta_shape_structs(meta, dtype, int_leaves=("pos",)):
    def mk(path, m):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        dt = jnp.int32 if name in int_leaves else dtype
        return jax.ShapeDtypeStruct(m.shape, dt)
    return jax.tree_util.tree_map_with_path(
        mk, meta, is_leaf=lambda x: isinstance(x, LeafMeta))


def meta_axes(meta):
    return jax.tree_util.tree_map(lambda m: m.axes, meta,
                                  is_leaf=lambda x: isinstance(x, LeafMeta))


def param_logical_axes(cfg):
    return meta_axes(param_meta(cfg))


# ------------------------------------------------------------------- init
def _init_leaf(key, m: LeafMeta, cfg, dtype):
    if m.init == "zeros":
        return jnp.zeros(m.shape, dtype)
    if m.init == "ones":
        return jnp.ones(m.shape, dtype)
    if m.init == "A_log":
        h = m.shape[-1]
        base = jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32))
        return jnp.broadcast_to(base, m.shape).astype(dtype)
    if m.init == "dt_bias":
        h = m.shape[-1]
        dt0 = jnp.linspace(1e-3, 1e-1, h, dtype=jnp.float32)
        base = jnp.log(jnp.expm1(dt0))
        return jnp.broadcast_to(base, m.shape).astype(dtype)
    std = 0.02 / np.sqrt(2.0 * cfg.n_layers) if m.init == "normal_out" \
        else 0.02
    return (jax.random.normal(key, m.shape, jnp.float32) * std).astype(dtype)


def init_params(cfg, key):
    meta = param_meta(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        meta, is_leaf=lambda x: isinstance(x, LeafMeta))
    dtype = jnp.dtype(cfg.param_dtype)
    out = [_init_leaf(jax.random.fold_in(key, i), m, cfg, dtype)
           for i, m in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def init_cache(cfg, batch: int, seq: int, dtype):
    meta = cache_meta(cfg, batch, seq)
    return jax.tree_util.tree_map(
        lambda m: jnp.zeros(m.shape, jnp.int32 if m.shape == () else dtype),
        meta, is_leaf=lambda x: isinstance(x, LeafMeta))


# ---------------------------------------------------------------- forward
def forward(params, cfg, tokens, *, mode="train", pos=0, cache=None,
            patches=None, cache_len=None, pages=None, attn_extent=None,
            want_logits=True, n_tok=None):
    """tokens: (B,S[,K]) int32. Returns {"logits","cache","aux"}.

    mode: "train" (full logits) | "prefill" (cache + last logits) |
    "decode" (S==1, cache updated at ``pos`` — a scalar, or a (B,) vector
    of per-slot positions for continuous batching, where every batch row
    decodes at its own depth) | "prefill_chunk" (cache-append prefill
    continuation: S chunk tokens written at [pos, pos+S) of an existing
    dense prefill cache — last-position logits, like "prefill") |
    "verify" (speculative decode: S window lanes per slot appended at
    per-slot positions ``pos`` (B,), lane validity masked by ``n_tok``
    (B,); logits for ALL S positions come back so the engine can accept
    the longest agreeing draft prefix — at S == 1 this is the decode
    tick's computation exactly).  The cache ``pos`` leaf is returned
    unchanged in verify mode: the engine owns acceptance, so position
    bookkeeping is host-authoritative there.

    pages: optional paged-KV descriptor for decode and verify —
    ``{"table": (B, pages_per_slot) int32, "page_size": int,
    "cache_len": int, "kernel": bool}``.  Linear attention cache leaves
    (``repro.models.blocks.paged_names``) are then stacked paged pools,
    (n_repeats, num_pages, page_size, ...); bounded leaves (SWA rings,
    SSM state) stay dense per-slot rows.  The layer scan carries the
    pools whole and adds ``"layer"``, the repeat's index, to the
    descriptor each mixer sees: the mixer scatters its new rows in place
    and gathers its pages at that index (repro.models.layers.page_gather),
    so no layer's pool is written back into a fresh stacked output and
    the pool is not copied.  Bounded leaves are scanned per layer as
    ``xs`` / ``ys``.  With ``"kernel"`` set, attention walks the block table
    inside the fused Pallas decode kernel (repro.kernels.paged_attention)
    instead of materialising the dense gather — same tokens, no dense K/V
    view.

    attn_extent (prefill_chunk only): static key extent — attention reads
    only the first ``attn_extent`` cache positions (must cover
    pos + S).  Bit-exact for any extent (masked lanes are exact zeros);
    without it each chunk pays the full cache_len extent.  want_logits
    (prefill_chunk only): False skips the LM head for non-final chunks.

    Donation contract: in the cache-updating modes ("decode", "verify",
    "prefill_chunk") every cache leaf comes back with exactly its input
    shape and dtype, each input leaf feeds exactly one in-place update,
    and ``pos`` stays int32 — so the serve engine's jits can pass
    ``donate_argnums`` on the cache argument and XLA aliases the whole
    pool in place (checked per block at trace time, see
    ``repro.models.layers.check_cache_invariant``).  A paged pool is a
    loop-carried buffer updated by in-place scatters, so the donated
    input is the buffer every layer writes: the step holds no second
    copy of it.
    """
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = embed_tokens(tokens, params["embed"], cfg, dt)
    if cfg.frontend == "vision_patches" and mode in ("train", "prefill"):
        assert patches is not None
        x = jnp.concatenate([patches.astype(dt), x], axis=1)
    elif mode == "prefill_chunk" and patches is not None:
        # vision chunked prefill: the patch prefix rides on the first
        # chunk only (later chunks continue at pos past the patches)
        x = jnp.concatenate([patches.astype(dt), x], axis=1)
    b, s, _ = x.shape
    if mode == "decode":
        positions = pos
    elif mode == "verify":
        positions = pos[:, None] + jnp.arange(s)        # (B,S) per-slot
    else:
        positions = pos + jnp.arange(s)
    if cfg.pos_emb == "sinusoidal":
        pp = jnp.asarray(positions)
        # per-slot decode positions (B,) -> (B, 1) so the embedding
        # broadcasts per row instead of across the batch
        pp = pp[:, None] if (mode == "decode" and pp.ndim == 1) \
            else jnp.atleast_1d(pp)
        x = x + sinusoidal_pos(pp, cfg.d_model).astype(dt)
    x = shard(x, "batch", "seq", "embed")

    with_cache = mode != "train"
    cache_in = mode in ("decode", "prefill_chunk", "verify")
    cache_blocks = cache["blocks"] if cache is not None else None
    # paged pools ride the carry whole and each layer indexes them at its
    # own layer index; every other cache leaf is scanned per layer
    carried = pages is not None and mode in ("decode", "verify")
    if carried:
        names = [paged_names(spec, pages["cache_len"])
                 for spec in cfg.pattern]
        pools = tuple({k: c for k, c in cb.items() if k in n}
                      for cb, n in zip(cache_blocks, names))
        cache_blocks = tuple({k: c for k, c in cb.items() if k not in n}
                             for cb, n in zip(cache_blocks, names))
    else:
        pools = ()

    def body(carry, xs):
        x, aux, pools = carry
        bp = xs[0]
        bc = xs[1] if cache_in else (None,) * len(cfg.pattern)
        pg = {**pages, "layer": xs[2]} if carried else pages
        new_cs, new_pools = [], []
        for i, spec in enumerate(cfg.pattern):
            c = {**bc[i], **pools[i]} if carried else bc[i]
            x, nc, a = block_apply(x, bp[i], cfg, spec, mode=mode, pos=pos,
                                   cache=c, cache_len=cache_len,
                                   pages=pg, attn_extent=attn_extent,
                                   n_tok=n_tok)
            if carried:
                new_pools.append({k: nc[k] for k in pools[i]})
                nc = {k: v for k, v in nc.items() if k not in pools[i]}
            new_cs.append(nc)
            aux = aux + a
        ys = tuple(new_cs) if with_cache else ()
        return (x, aux, tuple(new_pools)), ys

    if cfg.remat == "full":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)

    xs = (params["blocks"], cache_blocks) if cache_in \
        else (params["blocks"],)
    if carried:
        xs = xs + (jnp.arange(cfg.n_repeats),)
    (x, aux, pools), new_blocks = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), pools), xs)
    if carried:
        new_blocks = tuple({**nb, **pl} for nb, pl in zip(new_blocks, pools))

    new_cache = None
    if with_cache:
        if mode == "decode":
            new_pos = cache["pos"] + 1
        elif mode == "verify":
            new_pos = cache["pos"]          # host-authoritative positions
        elif mode == "prefill_chunk":
            new_pos = jnp.asarray(pos + s, jnp.int32)
        else:
            new_pos = jnp.asarray(s, jnp.int32)
        new_cache = {"pos": new_pos, "blocks": new_blocks}

    if mode == "train" and cfg.frontend == "vision_patches":
        x = x[:, cfg.n_patches:]
    if mode in ("prefill", "prefill_chunk"):
        x = x[:, -1:]
    if not want_logits:                 # non-final chunk: cache only
        return {"logits": None, "cache": new_cache, "aux": aux}
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_logits(x, params, cfg)
    return {"logits": logits, "cache": new_cache, "aux": aux}


class LM:
    """Thin OO wrapper used by examples/tests."""

    def __init__(self, cfg):
        self.cfg = cfg

    def init(self, key):
        return init_params(self.cfg, key)

    def __call__(self, params, tokens, **kw):
        return forward(params, self.cfg, tokens, **kw)
