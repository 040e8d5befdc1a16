"""Step builders: train_step (grad-accum + AdamW), prefill_step, serve_step.

All steps open the sharding context themselves, so lowering them under
``jax.jit`` with a mesh active resolves every internal constraint; with
``mesh=None`` they run as plain single-device functions (CPU tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .models.blocks import paged_names
from .models.lm import cache_meta, forward, init_params, init_cache
from .models.layers import softmax_xent
from .optim import OptHParams, adamw_init, adamw_update
from .sharding import sharding_ctx

DECODE_RULES = {"heads": ()}  # decode shards cache-seq, not heads

# Tensor-parallel serving: the KV pool shards on *heads* (kv_heads /
# ssm_heads / conv_dim -> model, straight from LOGICAL_RULES) and the
# sequence/page and slot axes stay replicated — the exact inverse of the
# legacy DECODE_RULES layout.  batch -> () keeps tokens, logits, block
# tables and per-slot pos replicated so the engine's host-side mirrors
# read them without a gather.
TP_SERVE_RULES = {"seq_shard": (), "batch": ()}

# Serve-cache leaf name -> logical axes (paged slot pools and batch=1/bpad
# prefill row caches all share leaf names and ranks, so one table covers
# every cache the engine moves between steps).  Leaves without a
# head-like dim (MLA latents, pos) resolve to fully replicated under
# TP_SERVE_RULES.
SERVE_CACHE_AXES: dict[str, tuple[str | None, ...]] = {
    "k": (None, "batch", "seq_shard", "kv_heads", None),
    "v": (None, "batch", "seq_shard", "kv_heads", None),
    "ckv": (None, "batch", "seq_shard", None),
    "krope": (None, "batch", "seq_shard", None),
    "conv": (None, "batch", None, "conv_dim"),
    "state": (None, "batch", "ssm_heads", None, None),
}


def serve_cache_axes(name: str, ndim: int) -> tuple[str | None, ...]:
    """Logical axes for one serve-cache leaf (replicated fallback for
    unknown names or rank mismatches — e.g. ``pos``)."""
    axes = SERVE_CACHE_AXES.get(name)
    if axes is None or len(axes) != ndim:
        return (None,) * ndim
    return axes


def cast_tree(tree, dtype):
    dt = jnp.dtype(dtype)
    return jax.tree.map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def init_train_state(cfg, key, hp: OptHParams = OptHParams()):
    params = init_params(cfg, key)
    return {
        "params": params,
        "opt": adamw_init(params, cfg.opt_dtype),
        "step": jnp.zeros((), jnp.int32),
    }


def _token_loss(out, labels, cfg):
    losses = softmax_xent(out["logits"], labels, cfg.z_loss)
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss


def make_train_step(cfg, mesh=None, hp: OptHParams = OptHParams()):
    """batch leaves are (accum, micro, ...) — scan over accum microbatches."""

    def loss_fn(params, micro):
        pc = cast_tree(params, cfg.dtype)
        out = forward(pc, cfg, micro["tokens"], mode="train",
                      patches=micro.get("patches"))
        loss = _token_loss(out, micro["labels"], cfg)
        total = loss + 0.01 * out["aux"] / max(cfg.n_layers, 1)
        return total, loss

    def train_step(state, batch):
        with sharding_ctx(mesh):
            params = state["params"]
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

            def micro_step(carry, micro):
                gsum, lsum = carry
                (_, loss), g = grad_fn(params, micro)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + loss), ()

            accum = jax.tree.leaves(batch)[0].shape[0]
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(micro_step,
                                           (g0, jnp.zeros((), jnp.float32)),
                                           batch)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            new_p, new_opt, metrics = adamw_update(
                grads, state["opt"], params, state["step"], hp)
            metrics["loss"] = lsum / accum
            new_state = {"params": new_p, "opt": new_opt,
                         "step": state["step"] + 1}
            return new_state, metrics

    return train_step


def make_prefill_step(cfg, mesh=None, cache_len=None, *, tp=False):
    rules = TP_SERVE_RULES if tp else None

    def prefill_step(params, tokens, patches=None):
        with sharding_ctx(mesh, rules):
            pc = cast_tree(params, cfg.dtype)
            out = forward(pc, cfg, tokens, mode="prefill", patches=patches,
                          cache_len=cache_len)
            return out["cache"], out["logits"]

    return prefill_step


def make_serve_step(cfg, mesh=None, *, tp=False, return_logits=False):
    """One decode step: (params, cache, tokens) -> (next_tokens, cache),
    or (logits, cache) with ``return_logits`` (teacher-forced
    comparisons read the logits the greedy choice was made from)."""
    rules = TP_SERVE_RULES if tp else DECODE_RULES

    def serve_step(params, cache, tokens):
        with sharding_ctx(mesh, rules):
            pc = cast_tree(params, cfg.dtype)
            out = forward(pc, cfg, tokens, mode="decode", pos=cache["pos"],
                          cache=cache)
            if return_logits:
                return out["logits"], out["cache"]
            nxt = jnp.argmax(out["logits"], axis=-1).astype(jnp.int32)
            return nxt, out["cache"]

    return serve_step


def greedy_oneshot(prefill, serve_step, params, prompts, patches, gen):
    """The one-shot greedy path: batched prefill, then ``gen - 1`` decode
    ticks; returns the (B, gen[, K]) token array.  The single reference
    implementation the engine equivalence tests and serve benchmarks
    compare against."""
    cache, logits = prefill(params, prompts, patches)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    for _ in range(gen - 1):
        tok, cache = serve_step(params, cache, tok)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


# ------------------------------------------------- continuous-batching steps
def chunkable(cfg, cache_len: int) -> bool:
    """Can prefill be chunked bit-exactly for this config?  Requires every
    block to be linear-cache attention: MoE routing capacity depends on
    the sequence extent (chunking would change drop behaviour), the
    chunked-SSD scan is tied to ``ssm_chunk`` boundaries, and a true SWA
    ring (window < cache_len) has no linear append."""
    for spec in cfg.pattern:
        if spec.kind == "ssm" or spec.mlp == "moe":
            return False
        if spec.window is not None and spec.window < cache_len:
            return False
    return True


def speculatable(cfg, cache_len: int) -> bool:
    """Can draft-verify speculative decoding be bit-exact for this config?

    The verify window rides the same seq-extent-invariance bar as chunked
    prefill (:func:`chunkable` — no MoE capacity coupling, no SSD scan
    boundaries, no true SWA ring), plus a scalar greedy-token frontend:
    audio codebook steps emit a K-vector per position, which the n-gram
    drafter and the longest-agreeing-prefix acceptance rule do not
    model."""
    return chunkable(cfg, cache_len) and cfg.frontend != "audio_codebooks"


def init_paged_slot_cache(cfg, slots: int, cache_len: int, dtype,
                          page_size: int, num_pages: int):
    """Slot cache with linear attention leaves replaced by paged pools.

    A paged leaf holds ``num_pages`` physical pages of ``page_size`` token
    slots — shape (n_repeats, num_pages, page_size, *tail) instead of
    (n_repeats, slots, cache_len, *tail) — addressed through a per-slot
    block table (held by the caller, see repro.serve.pager.PagePool).
    Page 0 is the reserved garbage page: dead slots and unallocated
    logical pages point there, so their scatters never corrupt a live
    slot.  Bounded leaves (SWA rings, SSM conv/state) keep one row per
    slot, as :func:`init_cache` lays them out for ``slots`` rows; ``pos``
    is a (slots,) vector — every slot decodes at its own depth.

    The pool stays stacked over the layer repeats: the decode and verify
    steps carry it whole through the layer scan and index it by layer
    in their scatters and gathers (:func:`repro.models.lm.forward`), so
    a step neither writes a layer's pool back nor copies the pool."""
    assert cache_len % page_size == 0, (cache_len, page_size)
    assert num_pages >= 2, "need at least one usable page + garbage page 0"
    dt = jnp.dtype(dtype)
    meta = cache_meta(cfg, slots, cache_len)
    blocks = []
    for spec, bm in zip(cfg.pattern, meta["blocks"]):
        paged = paged_names(spec, cache_len)
        leaves = {}
        for name, m in bm.items():
            if name in paged:
                assert m.shape[2] == cache_len, (name, m.shape)
                shape = (m.shape[0], num_pages, page_size) + m.shape[3:]
            else:
                shape = m.shape
            leaves[name] = jnp.zeros(shape, dt)
        blocks.append(leaves)
    return {"pos": jnp.zeros((slots,), jnp.int32), "blocks": tuple(blocks)}


def make_batched_insert_step(cfg, mesh=None, *, cache_len: int,
                             page_size: int, tp=False):
    """Insert row ``row`` of a *batched* prefill output into slot ``slot``
    of the shared paged cache:

        (cache, rows_cache, row, slot, table_row) -> cache

    ``table_row``: (pages_per_slot,) physical page ids for the slot;
    unreserved logical pages point at garbage page 0 (their scatters
    collide there and are never read valid).  Under on-demand paging the
    garbage tail is later re-pointed at real pages as the slot's ``pos``
    grows past a boundary (``KVState.grow_slot_pages``) — sound precisely
    because the tail positions were never written anywhere else: the
    decode scatter fills each page at the moment its position range
    first becomes live.  Bounded leaves are written at row ``slot``.

    ``rows_cache`` is a dense (B, cache_len) prefill/chunk cache; ``row``
    and ``slot`` may be traced scalars, so one jit covers every
    (row, slot) pair per batch shape.

    Donation: safe to jit with ``donate_argnums=(0,)`` (the pool cache;
    every leaf is a shape/dtype-preserving in-place write).  The
    ``rows_cache`` argument must **not** be donated — one prefill batch
    feeds one insert per row, so the same version is read repeatedly."""
    rules = TP_SERVE_RULES if tp else DECODE_RULES

    def insert_step(cache, rows_cache, row, slot, table_row):
        with sharding_ctx(mesh, rules):
            new_blocks = []
            for spec, cb, rb in zip(cfg.pattern, cache["blocks"],
                                    rows_cache["blocks"]):
                paged = paged_names(spec, cache_len)
                leaves = {}
                for name, c in cb.items():
                    r = jax.lax.dynamic_slice_in_dim(rb[name], row, 1,
                                                     axis=1)
                    if name in paged:
                        # (n_repeats, 1, cache_len, *tail) -> logical
                        # pages, scattered to the slot's physical pages
                        pps = cache_len // page_size
                        rr = r[:, 0].reshape(
                            (r.shape[0], pps, page_size) + r.shape[3:])
                        leaves[name] = c.at[:, table_row].set(
                            rr.astype(c.dtype))
                    else:
                        start = (0, slot) + (0,) * (c.ndim - 2)
                        leaves[name] = jax.lax.dynamic_update_slice(
                            c, r.astype(c.dtype), start)
                new_blocks.append(leaves)
            pos = cache["pos"].at[slot].set(
                rows_cache["pos"].astype(jnp.int32))
            return {"pos": pos, "blocks": tuple(new_blocks)}

    return insert_step


def make_prefix_gather_step(cfg, mesh=None, *, cache_len: int,
                            page_size: int, tp=False):
    """Materialise a batch-1 dense row cache from shared KV pages — the
    read half of a prefix-cache hit:

        (cache, table_row, pos) -> row_cache

    ``cache`` is the engine's paged pool; ``table_row`` is a
    (pages_per_slot,) physical page vector whose leading entries are the
    matched prefix pages (plus the copy-on-write fork source) and whose
    tail points at garbage page 0; ``pos`` (traced — one jit total) is
    the number of valid prefix tokens.  Positions past ``pos`` gather
    garbage-page content, which the chunked tail prefill overwrites or
    the position mask excludes — the same convention every paged read
    already relies on.  The gathered row then seeds
    :func:`make_prefill_chunk_step` at offset ``pos``: the prefix K/V
    are bit-identical to what the skipped chunks would have computed
    (they are a pure copy of pages an earlier identical-prefix prefill
    wrote), so the tail chunks — extent-invariant by the ``chunkable``
    gate — produce logits bit-identical to a cold prefill.

    Donation: the pool argument must **not** be donated — this is a pure
    read; the engine's live cache stays the single owner.  The output
    row is fresh and feeds the chunk chain as its first donated version.
    """
    assert cache_len % page_size == 0
    assert chunkable(cfg, cache_len), (
        f"{cfg.name}: prefix-cache gather rides the chunked-prefill "
        "machinery — non-chunkable configs bypass the prefix cache")
    pps = cache_len // page_size
    meta = cache_meta(cfg, 1, cache_len)
    rules = TP_SERVE_RULES if tp else DECODE_RULES

    def gather_step(cache, table_row, pos):
        with sharding_ctx(mesh, rules):
            blocks = []
            for spec, cb, bm in zip(cfg.pattern, cache["blocks"],
                                    meta["blocks"]):
                paged = paged_names(spec, cache_len)
                # the chunkable gate guarantees every leaf pages — a
                # bounded (ring/state) leaf here would need real content
                # this pool does not hold
                assert set(cb) == paged, (spec, set(cb), paged)
                leaves = {}
                for name, c in cb.items():
                    g = c[:, table_row]  # (n_rep, pps, page_size, *tail)
                    leaves[name] = g.reshape(
                        (c.shape[0], 1, pps * page_size) + c.shape[3:])
                blocks.append(leaves)
            return {"pos": jnp.asarray(pos, jnp.int32),
                    "blocks": tuple(blocks)}

    return gather_step


def make_decode_step(cfg, mesh=None, *, cache_len: int, page_size: int,
                     paged_kernel: bool = False, tp=False):
    """Masked continuous-batching decode over the slot pool:
    (params, cache, tokens, active, table) -> (next_tokens, cache).

    ``cache["pos"]`` is (slots,) per-slot positions; ``active`` is a
    (slots,) bool mask.  Dead slots emit token 0 and do not advance
    ``pos`` — their rows still flow through the batched matmuls (rows are
    independent, MoE capacity is per-row) but can never corrupt a live
    slot's sampling, and an insert replaces their whole row anyway.

    The linear attention leaves of ``cache`` are paged pools and
    ``table`` carries the (slots, pages_per_slot) block table; dead
    slots' tables point at garbage page 0, so their (frozen-``pos``)
    cache writes land there.  The table is a per-tick *argument*, not
    captured state, which is what lets the engine grow a live slot's row
    between ticks (on-demand paging) or re-point an evicted slot's row
    at garbage without recompiling — the jit sees the same shape either
    way.

    ``paged_kernel=True`` routes the paged attention leaves through the
    fused Pallas kernel: the block table is walked in-kernel and K/V
    pages are read in place instead of materialising the dense
    ``page_gather`` view every tick.  Greedy tokens are identical; the
    default-off dense-gather leg stays the A/B baseline and oracle.

    Donation: safe to jit with ``donate_argnums=(1,)`` — the forward
    pass preserves every cache leaf's shape/dtype (trace-time checked),
    so XLA aliases the whole pool in place and a tick stops copying it.
    Tokens/active/table are *not* donated: the engine keeps reading
    them (token streams, host mirrors) after the dispatch."""
    assert cache_len % page_size == 0, (cache_len, page_size)
    rules = TP_SERVE_RULES if tp else DECODE_RULES

    def decode_step(params, cache, tokens, active, table):
        with sharding_ctx(mesh, rules):
            pc = cast_tree(params, cfg.dtype)
            pages = {"table": table, "page_size": page_size,
                     "cache_len": cache_len, "kernel": paged_kernel}
            out = forward(pc, cfg, tokens, mode="decode", pos=cache["pos"],
                          cache=cache, pages=pages)
            nxt = jnp.argmax(out["logits"], axis=-1).astype(jnp.int32)
            amask = active.reshape((-1,) + (1,) * (nxt.ndim - 1))
            nxt = jnp.where(amask, nxt, 0)
            new_cache = out["cache"]
            new_cache["pos"] = jnp.where(active, cache["pos"] + 1,
                                         cache["pos"])
            return nxt, new_cache

    return decode_step


def make_verify_step(cfg, mesh=None, *, cache_len: int, page_size: int,
                     tp=False):
    """Draft-verify speculative decode over the paged slot pool:

        (params, cache, tokens, pos, n_tok, table) -> (argmax, cache)

    ``tokens`` is the (slots, S) verify window per slot — the last
    committed token followed by up to S-1 drafted tokens, right-padded;
    ``pos`` (slots,) int32 is the cache position the window starts at
    (the last committed token's KV, not yet written, goes there — decode
    has the same one-behind convention); ``n_tok`` (slots,) int32 is the
    valid window length per slot (0 = dead slot: every one of its writes
    lands on the garbage page / is dropped, and its argmax row is
    garbage the engine ignores).  The step cache-appends the whole
    window and scores all S positions in ONE device dispatch; the
    returned (slots, S) argmax at lane j is exactly the token
    tick-by-tick decode would emit after committing ``tokens[:, :j+1]``.
    Greedy acceptance of the longest agreeing draft prefix plus the
    first correction is therefore bit-identical to tick-by-tick decode
    *by construction*: the committed tokens ARE argmax outputs of the
    target model, never draft guesses.  At S == 1 the lowered
    computation is the decode tick's (same formulation — see
    :func:`repro.models.attention.verify_attention`); for S > 1 lane
    equality is seq-extent invariance, the property the ``chunkable``
    machinery already establishes on this backend.

    Rollback of rejected lanes is free: their cache writes sit at
    positions at or past the committed extent, which every later read
    position-masks out and the next window overwrites in place.

    ``pos`` rides as a per-dispatch *argument* (host-authoritative, like
    the block table): the engine owns acceptance, so the cache's
    ``pos`` leaf comes back unchanged.  Paged attention always takes the
    dense-gather oracle path here — the fused Pallas kernel is a
    single-query decode specialisation and stays on the decode leg.

    Donation: safe to jit with ``donate_argnums=(1,)`` — the same
    shape/dtype-preserving cache append as decode (trace-time checked).
    """
    assert cache_len % page_size == 0, (cache_len, page_size)
    assert speculatable(cfg, cache_len), (
        f"{cfg.name}: speculative decoding needs a chunk-exact config "
        "(no MoE, no SSM, no SWA ring shorter than cache_len) and a "
        "scalar greedy-token frontend")
    rules = TP_SERVE_RULES if tp else DECODE_RULES

    def verify_step(params, cache, tokens, pos, n_tok, table):
        with sharding_ctx(mesh, rules):
            pc = cast_tree(params, cfg.dtype)
            pages = {"table": table, "page_size": page_size,
                     "cache_len": cache_len, "kernel": False}
            out = forward(pc, cfg, tokens, mode="verify", pos=pos,
                          cache=cache, cache_len=cache_len, pages=pages,
                          n_tok=n_tok)
            nxt = jnp.argmax(out["logits"], axis=-1).astype(jnp.int32)
            return nxt, out["cache"]

    return verify_step


def make_prefill_chunk_step(cfg, mesh=None, cache_len: int | None = None, *,
                            tp=False):
    """Cache-append prefill continuation (chunked/preemptible prefill):

        (params, row_cache, tokens, q_off[, patches]) -> (row_cache,
        last-position logits)

    ``row_cache`` is a dense (B, cache_len) cache (start from
    ``init_cache``); ``tokens`` is the (B, C) chunk written at positions
    [q_off, q_off + C).  ``q_off`` may be traced — one jit per chunk
    *shape*, not per offset.  The final chunk's logits equal the one-shot
    prefill's bit-for-bit (masked key lanes are exact zeros); MoE/SSM/SWA
    ring patterns cannot chunk exactly — see :func:`chunkable`.

    Donation: safe to jit with ``donate_argnums=(1,)`` — each chunk
    consumes the previous chunk's ``row_cache`` version exactly once (a
    linear chain), and the cache-append writes preserve every leaf's
    shape/dtype."""
    assert cache_len is not None
    assert chunkable(cfg, cache_len), (
        f"{cfg.name}: chunked prefill needs linear-cache attention blocks "
        "(no MoE, no SSM, no SWA ring shorter than cache_len)")
    rules = TP_SERVE_RULES if tp else None

    def chunk_step(params, row_cache, tokens, q_off, patches=None, *,
                   attn_extent=None, want_logits=True):
        # attn_extent/want_logits are static (jit with
        # static_argnames): a per-chunk extent bucket keeps total
        # chunked FLOPs at the one-shot level, and non-final chunks skip
        # the LM head entirely
        with sharding_ctx(mesh, rules):
            pc = cast_tree(params, cfg.dtype)
            out = forward(pc, cfg, tokens, mode="prefill_chunk", pos=q_off,
                          cache=row_cache, patches=patches,
                          cache_len=cache_len, attn_extent=attn_extent,
                          want_logits=want_logits)
            return out["cache"], out["logits"]

    return chunk_step


__all__ = ["init_train_state", "make_train_step", "make_prefill_step",
           "make_serve_step", "make_decode_step",
           "make_batched_insert_step", "make_prefill_chunk_step",
           "make_prefix_gather_step", "make_verify_step",
           "init_paged_slot_cache", "paged_names",
           "chunkable", "speculatable", "greedy_oneshot", "cast_tree",
           "init_cache", "OptHParams", "TP_SERVE_RULES", "serve_cache_axes"]
