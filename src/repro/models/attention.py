"""Attention family: GQA (+QKV bias, sliding window) and MLA (latent KV).

Attention's ops carry one of four named scopes in their HLO metadata, so
a device profile splits attention time: ``attn.proj`` (norm, projections, rope,
MLA absorption, output projection), ``attn.kv_write`` (cache writes),
``attn.kv_gather`` (the paged leg's page gather) and ``attn.core``
(scores, softmax and the weighted sum, or the paged kernel).

Three execution paths, chosen by the step being lowered:
  * ``full``    — materialised scores, train-time (seq <= ~4k).
  * ``prefill`` — forward-only one-shot prefill: the tiled flash kernel on
    a TPU, a lax.scan over query chunks elsewhere (``prefill_attention``).
  * ``decode``  — single query token against a (sequence-sharded) cache.

KV caches are stored unexpanded (n_kv heads); GQA expands K/V to the query
heads at compute time (bytes are negligible, sharding stays clean).
SWA uses a ring cache of ``window`` slots; slot ``s`` holds absolute position
``pos - ((pos - s) mod W)`` so validity/masking need no bookkeeping array.
MLA decodes in the *absorbed* form (scores against the compressed latent),
so its cache is (c_kv, k_rope) — the architecture's whole point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..kernels import (flash_attention, paged_decode_attention,
                       paged_mla_decode_attention)
from ..sharding import axis_size, current_mesh, resolved_axes, shard
from .layers import (apply_rope, page_gather, page_scatter,
                     page_scatter_window, rms_norm, scope)

NEG_INF = -1e30


def _tp_kernel_axes(*head_counts: int) -> tuple[str, ...] | None:
    """Mesh axes for the per-shard kernel dispatch (paged decode, flash
    prefill), or ``None`` for the single-shard call.  The kernels are
    head-parallel, so a tensor-parallel layout (heads on the mesh,
    sequence replicated — TP_SERVE_RULES) dispatches one kernel per
    model shard via ``shard_map``; the legacy
    decode layout (seq_shard on the mesh) and non-dividing head counts
    (the constraint would drop or pad the axis) keep the plain call."""
    mesh = current_mesh()
    if mesh is None:
        return None
    if resolved_axes("seq_shard"):
        return None                      # pool is sequence-sharded (legacy)
    axes = resolved_axes("kv_heads")
    n = axis_size(mesh, axes)
    if n <= 1 or any(h % n for h in head_counts):
        return None
    return axes


def paged_leaf(pages, window, cache_len=None):
    """Static predicate: is this attention cache leaf a paged pool?

    Only *linear* caches are paged — ``window is None``, or the SWA ring
    degenerated to linear because ``window >= cache_len`` (slot == pos, no
    wraparound).  A true ring (window < cache_len) is already bounded, so
    it stays a dense per-slot row.  ``pages`` carries ``cache_len`` so the
    check stays static at trace time."""
    if pages is None:
        return False
    cl = pages["cache_len"] if cache_len is None else cache_len
    return window is None or window >= cl


import functools
import os

# REPRO_BASELINE_ATTN=1 restores the unoptimised (pre-§Perf) formulation so
# EXPERIMENTS.md can report before/after under one cost model.
_BASELINE = os.environ.get("REPRO_BASELINE_ATTN") == "1"


def _expand_kv_plain(k, n_heads):
    b, s, hkv, dh = k.shape
    if hkv == n_heads:
        return k
    rep = n_heads // hkv
    kx = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, rep, dh))
    return kx.reshape(b, s, n_heads, dh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _expand_kv_opt(k, n_heads):
    """(B, S, Hkv, Dh) -> (B, S, H, Dh) by group broadcast.

    custom_vjp: the natural backward (reshape + sum over the group dim)
    reshapes a head-sharded cotangent and forces a full activation
    all-gather when H % mesh != 0 (EXPERIMENTS.md §Perf it.3).  Instead the
    backward contracts against a constant 0/1 group matrix in the compute
    dtype: sharded partial sums + one small all-reduce.
    """
    b, s, hkv, dh = k.shape
    if hkv == n_heads:
        return k
    rep = n_heads // hkv
    kx = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, rep, dh))
    return kx.reshape(b, s, n_heads, dh)


def _expand_kv_fwd(k, n_heads):
    return _expand_kv_opt(k, n_heads), k


def _expand_kv_bwd(n_heads, k, g):
    hkv, dtype = k.shape[2], k.dtype
    if hkv == n_heads:
        return (g,)
    gmat = (jnp.arange(n_heads) // (n_heads // hkv) ==
            jnp.arange(hkv)[:, None]).astype(dtype)        # (Hkv, H)
    dk = jnp.einsum("bshd,kh->bskd", g.astype(dtype), gmat)
    return (shard(dk, "batch", "seq", "kv_heads", None),)


_expand_kv_opt.defvjp(_expand_kv_fwd, _expand_kv_bwd)

_expand_kv = _expand_kv_plain if _BASELINE else _expand_kv_opt


def _mask_bias(sq, sk, q_off, window):
    """(sq, sk) additive causal(+window) mask. q position = q_off + i."""
    qi = q_off + jnp.arange(sq)[:, None]
    kj = jnp.arange(sk)[None, :]
    ok = kj <= qi
    if window is not None:
        ok &= kj > qi - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias, scale=None):
    """q: (B,Sq,H,Dh) k/v: (B,Sk,H,Dh) bias: (Sq,Sk). f32 softmax."""
    scale = (q.shape[-1] ** -0.5) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * scale + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@scope("attn.core")
def full_attention(q, k, v, *, window=None, q_off=0, scale=None):
    k = _expand_kv(k, q.shape[2])
    v = _expand_kv(v, q.shape[2])
    bias = _mask_bias(q.shape[1], k.shape[1], q_off, window)
    out = _sdpa(q, k, v, bias, scale)
    return shard(out, "batch", "seq", "heads", "head_dim")


@scope("attn.core")
def qchunk_attention(q, k, v, *, window=None, chunk=512, scale=None):
    """Forward-only prefill: scan over query chunks vs full K/V."""
    b, s, h, dh = q.shape
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    n = max(1, s // chunk)
    chunk = s // n
    assert n * chunk == s, (s, chunk)
    qs = q.reshape(b, n, chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def body(i, qc):
        bias = _mask_bias(chunk, s, i * chunk, window)
        return i + 1, _sdpa(qc, k, v, bias, scale)

    _, outs = jax.lax.scan(body, 0, qs)
    dv = v.shape[-1]
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dv)
    return shard(out, "batch", "seq", "heads", "head_dim")


def _backend() -> str:
    return jax.default_backend()


def prefill_attention(q, k, v, *, window=None, scale=None):
    """One-shot causal (+window) prefill attention: q (B,S,H,Dqk), k
    (B,S,Hkv,Dqk), v (B,S,Hkv,Dv) -> (B,S,H,Dv).

    On a TPU (the backend :func:`_backend` observes) the tiled flash
    kernel runs it: scores stay in VMEM, and key blocks above the
    diagonal are neither fetched nor multiplied.  Elsewhere
    :func:`qchunk_attention` runs it, the formulation the CPU
    bit-identity tests pin.  A ``pallas_call`` has no partitioning rule,
    so under a tensor-parallel mesh (heads on it) one kernel runs per
    head shard through ``shard_map``."""
    if _backend() != "tpu":
        return qchunk_attention(q, k, v, window=window, scale=scale)
    fn = functools.partial(flash_attention, causal=True, window=window,
                           scale=scale)
    axes = _tp_kernel_axes(q.shape[2], k.shape[2])
    with jax.named_scope("attn.core"):
        if axes:
            # heads are kv-head-major contiguous, so an even head split
            # keeps every query head on the shard holding its kv head
            hspec = P(None, None, axes, None)
            out = jax.shard_map(fn, mesh=current_mesh(),
                                in_specs=(hspec, hspec, hspec),
                                out_specs=hspec, check_vma=False)(q, k, v)
        else:
            out = fn(q, k, v)
    return shard(out, "batch", "seq", "heads", "head_dim")


@scope("attn.core")
def decode_attention(q, k_cache, v_cache, pos, *, window=None, scale=None):
    """q: (B,1,H,Dh); caches: (B,Sc,Hkv,Dh) sequence-sharded; pos is a
    scalar (all rows at the same position) or (B,) per-slot positions
    (continuous batching: every slot decodes at its own depth).

    Partial-softmax formulation: every op reduces *over* the sharded
    sequence dim (max/sum/contraction -> small ARs), so XLA never needs to
    gather the cache itself (EXPERIMENTS.md §Perf mixtral-decode it.1).
    """
    b, _, h, dh = q.shape
    sc = k_cache.shape[1]
    kf = shard(_expand_kv(k_cache, h), "batch", "seq_shard", "heads", None)
    vf = shard(_expand_kv(v_cache, h), "batch", "seq_shard", "heads", None)
    slots = jnp.arange(sc)
    per_slot = jnp.ndim(pos) == 1
    pp = pos[:, None] if per_slot else pos          # (B,1) | scalar
    if window is None:
        valid = slots <= pp
    else:
        slot_pos = pp - jnp.mod(pp - slots, sc)     # ring: sc == window
        valid = slot_pos >= 0
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    bias = bias[:, None, None, :] if per_slot else bias[None, None, None, :]
    scale = (dh ** -0.5) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
    scores = scores * scale + bias
    if _BASELINE:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    scores = shard(scores, "batch", "heads", None, "seq_shard")
    m = jnp.max(scores, axis=-1, keepdims=True)          # reduce over shard
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)               # reduce over shard
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vf)
    return out / jnp.swapaxes(l, 1, 2).astype(q.dtype)   # (B,1,H,1)


@scope("attn.core")
def verify_attention(q, k_cache, v_cache, pos, *, scale=None):
    """Speculative-decode verify: q: (B,S,H,Dh) — S window lanes per slot,
    lane j at position ``pos_b + j``; caches: (B,Sc,Hkv,Dh) already holding
    the window's K/V.  Linear caches only (the speculatable gate excludes
    true SWA rings).

    Deliberately the *same formulation* as :func:`decode_attention` — the
    identical einsum contractions and the identical partial-softmax
    (max/exp/sum over the sharded sequence dim, divide at the end) — just
    with a per-lane causal mask instead of a per-slot one.  At S == 1 the
    two lowerings compute the same reductions over the same extents, so a
    verify step with no drafted tokens IS the decode tick bit-for-bit;
    that is the base of the spec-decode bit-identity argument (the
    inductive step is seq-extent invariance, the chunked-prefill
    property)."""
    b, s, h, dh = q.shape
    sc = k_cache.shape[1]
    kf = shard(_expand_kv(k_cache, h), "batch", "seq_shard", "heads", None)
    vf = shard(_expand_kv(v_cache, h), "batch", "seq_shard", "heads", None)
    qp = pos[:, None] + jnp.arange(s)                      # (B,S)
    valid = jnp.arange(sc)[None, None, :] <= qp[:, :, None]  # (B,S,T)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None]
    scale = (dh ** -0.5) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
    scores = scores * scale + bias
    if _BASELINE:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    scores = shard(scores, "batch", "heads", None, "seq_shard")
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vf)
    return out / jnp.swapaxes(l, 1, 2).astype(q.dtype)     # (B,S,H,1)


# ====================================================================== GQA
def gqa_param_shapes(cfg):
    """Weights are stored FLAT — (d, H*dh) — so pjit argument shardings
    divide evenly for any head count; the model reshapes them head-split
    at use sites (a cheap per-layer weight reshard for H % mesh != 0) and
    keeps *activations* head-split end-to-end, so a sharded head dim is
    never reshaped into a flat feature dim (which would force a full
    activation all-gather; EXPERIMENTS.md §Perf iterations 2-3)."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "ln": ((d,), (None,), "ones"),
        "wq": ((d, h * dh), ("fsdp", "tp"), "normal"),
        "wk": ((d, hkv * dh), ("fsdp", "tp"), "normal"),
        "wv": ((d, hkv * dh), ("fsdp", "tp"), "normal"),
        "wo": ((h * dh, d), ("tp", "fsdp"), "normal"),
    }
    if cfg.qkv_bias:
        shapes["bq"] = ((h * dh,), ("tp",), "zeros")
        shapes["bk"] = ((hkv * dh,), ("tp",), "zeros")
        shapes["bv"] = ((hkv * dh,), ("tp",), "zeros")
    return shapes


def gqa_cache_shapes(cfg, spec, batch, seq):
    sc = min(seq, spec.window) if spec.window else seq
    kv = (batch, sc, cfg.n_kv_heads, cfg.head_dim)
    ax = ("batch", "seq_shard", "kv_heads", None)
    return {"k": (kv, ax), "v": (kv, ax)}


@scope("attn.kv_write")
def _cache_update(c, u, idx):
    """Write the decode-step update ``u`` (B,1,...) into cache ``c``
    (B,Sc,...) at sequence index ``idx`` — scalar (one shared position)
    or (B,) per-slot positions (a batched scatter; rows are independent,
    so a continuous-batching engine can hold slots at different depths).

    The update is cast to the cache dtype and the result keeps ``c``'s
    exact shape — the donation contract (the serve engine donates the
    cache into the decode jit; an in-place scatter is precisely the op
    XLA aliases)."""
    assert u.shape[0] == c.shape[0] and u.shape[2:] == c.shape[2:], (u.shape,
                                                                    c.shape)
    if jnp.ndim(idx) == 0:
        return jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (0, idx) + (0,) * (c.ndim - 2))
    return c.at[jnp.arange(c.shape[0]), idx].set(u[:, 0].astype(c.dtype))


@scope("attn.kv_write")
def _cache_update_window(c, u, pos, n_tok):
    """Dense-cache counterpart of :func:`page_scatter_window`: write the
    verify window ``u`` (B,S,...) into cache ``c`` (B,Sc,...) at per-slot
    positions ``pos_b + j`` for lanes ``j < n_tok_b``.  Masked lanes are
    redirected past the cache extent, where JAX's default scatter OOB
    mode drops them — padding never lands."""
    b, s = u.shape[:2]
    idx = pos[:, None] + jnp.arange(s)                     # (B,S)
    idx = jnp.where(jnp.arange(s)[None, :] < n_tok[:, None], idx, c.shape[1])
    return c.at[jnp.arange(b)[:, None], idx].set(u.astype(c.dtype))


@scope("attn.kv_write")
def _pad_seq(t, target):
    """Right-pad dim 1 (sequence) with zeros up to `target` slots."""
    if target is None or t.shape[1] >= target:
        return t
    pad = [(0, 0)] * t.ndim
    pad[1] = (0, target - t.shape[1])
    return jnp.pad(t, pad)


def gqa_apply(x, p, cfg, spec, *, mode, pos, cache=None, cache_len=None,
              pages=None, attn_extent=None, n_tok=None):
    """x: (B,S,D) -> (out, new_cache or None). cache: {"k","v"} unexpanded.

    With ``pages`` (decode and verify) the linear K/V leaves are the
    stacked paged pools (n_repeats, P, page_size, Hkv, Dh) that ride the
    layer scan's carry, and ``pages["layer"]`` is this layer's index: the
    new token's K/V is scattered through the block table at that layer
    and attention runs over a gathered slot-major dense view —
    bit-identical to the unpaged cache, since every valid (masked-in)
    position gathers the very value the dense cache would hold.  With
    ``pages["kernel"]`` the gather is replaced by the fused
    :func:`repro.kernels.paged_decode_attention` Pallas kernel, which
    reads the layer's pages in place (same greedy tokens, no dense copy).
    """
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    d = x.shape[-1]
    with jax.named_scope("attn.proj"):
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", xn,
                       p["wq"].astype(dt).reshape(d, h, dh))
        k = jnp.einsum("bsd,dhk->bshk", xn,
                       p["wk"].astype(dt).reshape(d, hkv, dh))
        v = jnp.einsum("bsd,dhk->bshk", xn,
                       p["wv"].astype(dt).reshape(d, hkv, dh))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(dt).reshape(h, dh)
            k = k + p["bk"].astype(dt).reshape(hkv, dh)
            v = v + p["bv"].astype(dt).reshape(hkv, dh)

    if mode == "decode":
        rp = pos[:, None] if jnp.ndim(pos) == 1 else pos   # (B,1) | scalar
        if cfg.pos_emb == "rope":
            q = apply_rope(q, rp, cfg.rope_theta)
            k = apply_rope(k, rp, cfg.rope_theta)
        kc, vc = cache["k"], cache["v"]
        w = spec.window
        if paged_leaf(pages, w):
            # linear logical index (ring degenerate: no wraparound), so
            # the scatter goes straight through the block table
            table, ps, l = pages["table"], pages["page_size"], pages["layer"]
            kc = page_scatter(kc, l, table, ps, pos, k)
            vc = page_scatter(vc, l, table, ps, pos, v)
            if pages.get("kernel"):
                # fused path: the Pallas kernel walks the block table
                # in-kernel and reads pages in place — page_gather's
                # dense slot-major copy never exists.  It takes one
                # layer's pool, so this leg slices ``kc[l]`` out
                pv = pos if jnp.ndim(pos) == 1 else jnp.full((b,), pos)
                axes = _tp_kernel_axes(h, hkv)
                with jax.named_scope("attn.core"):
                    if axes:
                        # tensor-parallel pool: one kernel per model
                        # shard.  Heads are kv-head-major contiguous, so
                        # an even head split keeps every query head on
                        # the shard holding its KV group — the kernel's
                        # group math is local and per-head outputs are
                        # bit-identical to the single-shard call.
                        hspec = P(None, None, axes, None)
                        out = jax.shard_map(
                            functools.partial(paged_decode_attention,
                                              page_size=ps, window=w),
                            mesh=current_mesh(),
                            in_specs=(hspec, hspec, hspec, P(None, None),
                                      P(None)),
                            out_specs=hspec, check_vma=False,
                        )(q, kc[l], vc[l], table, pv)
                    else:
                        out = paged_decode_attention(
                            q, kc[l], vc[l], table, pv, page_size=ps,
                            window=w)
            else:
                kd = shard(page_gather(kc, l, table, ps),
                           "batch", "seq_shard", "kv_heads", None)
                vd = shard(page_gather(vc, l, table, ps),
                           "batch", "seq_shard", "kv_heads", None)
                out = decode_attention(q, kd, vd, pos, window=w)
        else:
            idx = jnp.mod(pos, kc.shape[1]) if w is not None else pos
            kc = _cache_update(kc, k, idx)
            vc = _cache_update(vc, v, idx)
            kc = shard(kc, "batch", "seq_shard", "kv_heads", None)
            vc = shard(vc, "batch", "seq_shard", "kv_heads", None)
            out = decode_attention(q, kc, vc, pos, window=w)
        new_cache = {"k": kc, "v": vc}
    elif mode == "verify":
        # speculative-decode verify window: S lanes per slot at positions
        # pos_b + j, lanes >= n_tok_b masked (padding / dead slots).  The
        # speculatable gate guarantees a linear cache (window is None or
        # the degenerate ring), so logical index == position.  Paged
        # attention always takes the gather path here — the fused kernel
        # is a single-query decode specialisation and stays off the
        # verify leg (the gather path is its token-equality oracle).
        qp = pos[:, None] + jnp.arange(s)                  # (B,S)
        if cfg.pos_emb == "rope":
            q = apply_rope(q, qp, cfg.rope_theta)
            k = apply_rope(k, qp, cfg.rope_theta)
        kc, vc = cache["k"], cache["v"]
        if paged_leaf(pages, spec.window):
            table, ps, l = pages["table"], pages["page_size"], pages["layer"]
            kc = page_scatter_window(kc, l, table, ps, pos, k, n_tok)
            vc = page_scatter_window(vc, l, table, ps, pos, v, n_tok)
            kd = shard(page_gather(kc, l, table, ps),
                       "batch", "seq_shard", "kv_heads", None)
            vd = shard(page_gather(vc, l, table, ps),
                       "batch", "seq_shard", "kv_heads", None)
            out = verify_attention(q, kd, vd, pos)
        else:
            kc = _cache_update_window(kc, k, pos, n_tok)
            vc = _cache_update_window(vc, v, pos, n_tok)
            kc = shard(kc, "batch", "seq_shard", "kv_heads", None)
            vc = shard(vc, "batch", "seq_shard", "kv_heads", None)
            out = verify_attention(q, kc, vc, pos)
        new_cache = {"k": kc, "v": vc}
    else:
        q = shard(q, "batch", "seq", "heads", "head_dim")
        positions = pos + jnp.arange(s)
        if cfg.pos_emb == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        if mode == "prefill_chunk":
            # cache-append chunk (Sarathi-style): write this chunk's K/V
            # into the dense row cache at [pos, pos+s), then attend the
            # chunk's queries over the full cache extent, causally masked
            # at pos+i.  Masked lanes contribute exact zeros, so rows are
            # bit-identical to the one-shot prefill (the padded key
            # extent cannot perturb them); needs a linear cache — the
            # pattern is validated by make_prefill_chunk_step.
            kc, vc = cache["k"], cache["v"]
            start = (0, pos) + (0,) * (kc.ndim - 2)
            with jax.named_scope("attn.kv_write"):
                kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                                  start)
                vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                                  start)
            kc = shard(kc, "batch", "seq_shard", "kv_heads", None)
            vc = shard(vc, "batch", "seq_shard", "kv_heads", None)
            # static extent bucket: attend only the prefix that can hold
            # valid keys — any extent >= pos+s is bit-exact, and a
            # per-chunk bucket keeps chunked-prefill FLOPs at the
            # one-shot level instead of cache_len per chunk
            ext = kc.shape[1] if attn_extent is None else attn_extent
            out = full_attention(q, kc[:, :ext], vc[:, :ext], q_off=pos)
            new_cache = {"k": kc, "v": vc}
        elif mode == "prefill":
            out = prefill_attention(q, k, v, window=spec.window)
            w = spec.window
            if w is not None:
                # the ring only needs min(window, cache_len) slots: with
                # total length capped at cache_len no token can be older
                # than the window before the cache itself runs out
                ring = w if cache_len is None else min(w, cache_len)
                if s >= ring:
                    with jax.named_scope("attn.kv_write"):  # slot=pos%W
                        kc, vc = k[:, s - ring:], v[:, s - ring:]
                else:
                    kc, vc = _pad_seq(k, ring), _pad_seq(v, ring)
            else:
                kc, vc = _pad_seq(k, cache_len), _pad_seq(v, cache_len)
            new_cache = {
                "k": shard(kc, "batch", "seq_shard", "kv_heads", None),
                "v": shard(vc, "batch", "seq_shard", "kv_heads", None),
            }
        else:
            out = full_attention(q, k, v, window=spec.window)
            new_cache = None

    with jax.named_scope("attn.proj"):
        out = jnp.einsum("bshk,hkd->bsd", out,
                         p["wo"].astype(dt).reshape(h, dh, x.shape[-1]))
    return shard(out, "batch", "seq", "embed"), new_cache


# ====================================================================== MLA
def mla_param_shapes(cfg):
    d, h = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "ln": ((d,), (None,), "ones"),
        "wq_a": ((d, rq), ("fsdp", None), "normal"),
        "q_ln": ((rq,), (None,), "ones"),
        "wq_b": ((rq, h * (dn + dr)), (None, "tp"), "normal"),
        "wkv_a": ((d, rkv + dr), ("fsdp", None), "normal"),
        "kv_ln": ((rkv,), (None,), "ones"),
        "wk_b": ((rkv, h * dn), (None, "tp"), "normal"),
        "wv_b": ((rkv, h * dv), (None, "tp"), "normal"),
        "wo": ((h * dv, d), ("tp", "fsdp"), "normal"),
    }


def mla_cache_shapes(cfg, spec, batch, seq):
    return {
        "ckv": ((batch, seq, cfg.kv_lora_rank), ("batch", "seq_shard", None)),
        "krope": ((batch, seq, cfg.qk_rope_dim),
                  ("batch", "seq_shard", None)),
    }


def _mla_q(xn, p, cfg, dt):
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    rq = cfg.q_lora_rank
    qa = rms_norm(jnp.einsum("bsd,dr->bsr", xn, p["wq_a"].astype(dt)),
                  p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", qa,
                   p["wq_b"].astype(dt).reshape(rq, h, dn + dr))
    return q[..., :dn], q[..., dn:]          # q_nope, q_rope


def mla_apply(x, p, cfg, spec, *, mode, pos, cache=None, cache_len=None,
              pages=None, attn_extent=None, n_tok=None):
    b, s, _ = x.shape
    h = cfg.n_heads
    rkv, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                       cfg.v_head_dim)
    dt = x.dtype
    scale = (dn + dr) ** -0.5
    with jax.named_scope("attn.proj"):
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        q_nope, q_rope = _mla_q(xn, p, cfg, dt)
        kva = jnp.einsum("bsd,dr->bsr", xn, p["wkv_a"].astype(dt))
        ckv = rms_norm(kva[..., :rkv], p["kv_ln"], cfg.norm_eps)  # (B,S,rkv)
        k_rope = kva[..., rkv:]                                   # (B,S,dr)

    if mode == "decode":
        # absorbed decode: scores live in the latent space.
        per_slot = jnp.ndim(pos) == 1
        rp = pos[:, None] if per_slot else pos             # (B,1) | scalar
        q_rope = apply_rope(q_rope, rp, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None, :], rp,
                            cfg.rope_theta)[:, :, 0, :]
        cc, kr = cache["ckv"], cache["krope"]
        fused = paged_leaf(pages, None) and pages.get("kernel")
        if paged_leaf(pages, None):
            table, ps, l = pages["table"], pages["page_size"], pages["layer"]
            cc = page_scatter(cc, l, table, ps, pos, ckv)
            kr = page_scatter(kr, l, table, ps, pos, k_rope)
            if not fused:
                cd = shard(page_gather(cc, l, table, ps),
                           "batch", "seq_shard", None)
                kd = shard(page_gather(kr, l, table, ps),
                           "batch", "seq_shard", None)
        else:
            cc = _cache_update(cc, ckv, pos)
            kr = _cache_update(kr, k_rope, pos)
            cc = shard(cc, "batch", "seq_shard", None)
            kr = shard(kr, "batch", "seq_shard", None)
            cd, kd = cc, kr
        with jax.named_scope("attn.proj"):
            wk_b = p["wk_b"].astype(dt).reshape(rkv, h, dn)
            # absorb q_nope through wk_b:  (B,1,H,rkv)
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wk_b)
        with jax.named_scope("attn.core"):
            if fused:
                # fused paged path: latent pages read in place (the
                # absorbed form's V is its K, so the kernel returns the
                # attended latent and wv_b applies outside); the kernel
                # takes one layer's pools, sliced out at ``l``
                pv = pos if per_slot else jnp.full((b,), pos)
                axes = _tp_kernel_axes(h)
                if axes:
                    # tensor-parallel MLA: the latent pools carry no head
                    # dim (replicated); only the query splits, one kernel
                    # per model shard over its local query heads
                    hspec = P(None, None, axes, None)
                    rep3 = P(None, None, None)
                    lat = jax.shard_map(
                        functools.partial(paged_mla_decode_attention,
                                          page_size=ps, scale=scale),
                        mesh=current_mesh(),
                        in_specs=(hspec, hspec, rep3, rep3, P(None, None),
                                  P(None)),
                        out_specs=hspec, check_vma=False,
                    )(q_lat, q_rope, cc[l], kr[l], table, pv)
                else:
                    lat = paged_mla_decode_attention(
                        q_lat, q_rope, cc[l], kr[l], table, pv,
                        page_size=ps, scale=scale)
            else:
                scores = (jnp.einsum("bshr,btr->bhst", q_lat, cd) +
                          jnp.einsum("bshr,btr->bhst", q_rope, kd))
                scores = scores.astype(jnp.float32) * scale
                valid = jnp.arange(cd.shape[1]) <= rp      # (B,T) | (T,)
                mb = jnp.where(valid, 0.0, NEG_INF)
                scores = scores + (mb[:, None, None, :] if per_slot
                                   else mb[None, None, None, :])
                probs = jax.nn.softmax(scores, axis=-1).astype(dt)
                lat = jnp.einsum("bhst,btr->bshr", probs, cd)  # (B,1,H,rkv)
        with jax.named_scope("attn.proj"):
            out = jnp.einsum("bshr,rhv->bshv", lat,
                             p["wv_b"].astype(dt).reshape(rkv, h, dv))
        new_cache = {"ckv": cc, "krope": kr}
    elif mode == "verify":
        # speculative-decode verify: the ABSORBED decode form generalised
        # to S window lanes (NOT the non-absorbed chunk form — bit-identity
        # with tick-by-tick decode demands the same latent-space math the
        # decode tick runs).  The score einsums are unchanged: "bshr" was
        # already S-capable; only the mask gains a per-lane axis.
        qp = pos[:, None] + jnp.arange(s)                  # (B,S)
        q_rope = apply_rope(q_rope, qp, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None, :], qp,
                            cfg.rope_theta)[:, :, 0, :]
        cc, kr = cache["ckv"], cache["krope"]
        if paged_leaf(pages, None):
            table, ps, l = pages["table"], pages["page_size"], pages["layer"]
            cc = page_scatter_window(cc, l, table, ps, pos, ckv, n_tok)
            kr = page_scatter_window(kr, l, table, ps, pos, k_rope, n_tok)
            cd = shard(page_gather(cc, l, table, ps), "batch", "seq_shard",
                       None)
            kd = shard(page_gather(kr, l, table, ps), "batch", "seq_shard",
                       None)
        else:
            cc = _cache_update_window(cc, ckv, pos, n_tok)
            kr = _cache_update_window(kr, k_rope, pos, n_tok)
            cc = shard(cc, "batch", "seq_shard", None)
            kr = shard(kr, "batch", "seq_shard", None)
            cd, kd = cc, kr
        with jax.named_scope("attn.proj"):
            wk_b = p["wk_b"].astype(dt).reshape(rkv, h, dn)
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wk_b)  # (B,S,H,rkv)
        with jax.named_scope("attn.core"):
            scores = (jnp.einsum("bshr,btr->bhst", q_lat, cd) +
                      jnp.einsum("bshr,btr->bhst", q_rope, kd))
            scores = scores.astype(jnp.float32) * scale
            valid = (jnp.arange(cd.shape[1])[None, None, :]
                     <= qp[:, :, None])
            mb = jnp.where(valid, 0.0, NEG_INF)            # (B,S,T)
            scores = scores + mb[:, None]                  # (B,1,S,T)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            lat = jnp.einsum("bhst,btr->bshr", probs, cd)  # (B,S,H,rkv)
        with jax.named_scope("attn.proj"):
            out = jnp.einsum("bshr,rhv->bshv", lat,
                             p["wv_b"].astype(dt).reshape(rkv, h, dv))
        new_cache = {"ckv": cc, "krope": kr}
    elif mode == "prefill_chunk":
        # cache-append chunk: write this chunk's latent into the dense row
        # cache, then run the one-shot prefill form (non-absorbed) with
        # K/V reconstructed from the *full* cached latent — row-wise
        # identical to computing them from the chunk activations, and the
        # padded key extent is causally masked to exact zeros.
        positions = pos + jnp.arange(s)
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None, :], positions,
                            cfg.rope_theta)[:, :, 0, :]
        cc, kr = cache["ckv"], cache["krope"]
        with jax.named_scope("attn.kv_write"):
            cc = jax.lax.dynamic_update_slice(cc, ckv.astype(cc.dtype),
                                              (0, pos, 0))
            kr = jax.lax.dynamic_update_slice(kr, k_rope.astype(kr.dtype),
                                              (0, pos, 0))
        cc = shard(cc, "batch", "seq_shard", None)
        kr = shard(kr, "batch", "seq_shard", None)
        # static extent bucket (see gqa chunk branch): reconstruct and
        # attend only the key prefix that can be valid
        sk = cc.shape[1] if attn_extent is None else attn_extent
        with jax.named_scope("attn.proj"):
            k_nope = jnp.einsum("bsr,rhk->bshk", cc[:, :sk].astype(dt),
                                p["wk_b"].astype(dt).reshape(rkv, h, dn))
            vfull = jnp.einsum("bsr,rhk->bshk", cc[:, :sk].astype(dt),
                               p["wv_b"].astype(dt).reshape(rkv, h, dv))
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope,
                 jnp.broadcast_to(kr[:, :sk].astype(dt)[:, :, None, :],
                                  (b, sk, h, dr))], axis=-1)
        q = shard(q, "batch", "seq", "heads", "head_dim")
        k = shard(k, "batch", "seq", "heads", "head_dim")
        out = full_attention(q, k, vfull, q_off=pos, scale=scale)
        new_cache = {"ckv": cc, "krope": kr}
    else:
        positions = pos + jnp.arange(s)
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None, :], positions,
                            cfg.rope_theta)[:, :, 0, :]
        with jax.named_scope("attn.proj"):
            k_nope = jnp.einsum("bsr,rhk->bshk", ckv,
                                p["wk_b"].astype(dt).reshape(rkv, h, dn))
            vfull = jnp.einsum("bsr,rhk->bshk", ckv,
                               p["wv_b"].astype(dt).reshape(rkv, h, dv))
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                          (b, s, h, dr))], axis=-1)
        q = shard(q, "batch", "seq", "heads", "head_dim")
        k = shard(k, "batch", "seq", "heads", "head_dim")
        if mode == "prefill":
            out = prefill_attention(q, k, vfull, scale=scale)
            new_cache = {
                "ckv": shard(_pad_seq(ckv, cache_len),
                             "batch", "seq_shard", None),
                "krope": shard(_pad_seq(k_rope, cache_len),
                               "batch", "seq_shard", None),
            }
        else:
            out = full_attention(q, k, vfull, scale=scale)
            new_cache = None

    with jax.named_scope("attn.proj"):
        out = jnp.einsum("bshk,hkd->bsd", out,
                         p["wo"].astype(dt).reshape(h, dv, x.shape[-1]))
    return shard(out, "batch", "seq", "embed"), new_cache
