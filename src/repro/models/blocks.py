"""Residual block = mixer (attention or SSD) + MLP (dense / MoE / none)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from .layers import check_cache_invariant, mlp_dense, rms_norm
from .moe import moe_mlp, moe_mlp_ragged, moe_param_shapes
from .ssm import ssm_apply, ssm_cache_shapes, ssm_param_shapes


def _mixer(spec):
    if spec.kind == "ssm":
        return ssm_param_shapes, ssm_cache_shapes, ssm_apply
    if spec.attn == "mla":
        return (attn_mod.mla_param_shapes, attn_mod.mla_cache_shapes,
                attn_mod.mla_apply)
    return (attn_mod.gqa_param_shapes, attn_mod.gqa_cache_shapes,
            attn_mod.gqa_apply)


def dense_mlp_shapes(cfg):
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln": ((d,), (None,), "ones"),
        "w_gate": ((d, f), ("fsdp", "tp"), "normal"),
        "w_up": ((d, f), ("fsdp", "tp"), "normal"),
        "w_down": ((f, d), ("tp", "fsdp"), "normal"),
    }


def block_param_shapes(cfg, spec):
    shapes_fn, _, _ = _mixer(spec)
    out = {"mixer": shapes_fn(cfg)}
    if spec.mlp == "dense":
        out["mlp"] = dense_mlp_shapes(cfg)
    elif spec.mlp == "moe":
        out["mlp"] = moe_param_shapes(cfg)
    return out


def block_cache_shapes(cfg, spec, batch, seq):
    _, cache_fn, _ = _mixer(spec)
    return cache_fn(cfg, spec, batch, seq)


def block_apply(x, p, cfg, spec, *, mode, pos, cache=None, cache_len=None,
                pages=None, attn_extent=None, n_tok=None):
    """Returns (x, new_cache, aux_loss).  ``pages`` is the paged-KV
    descriptor threaded verbatim to the mixer (see repro.models.lm.forward
    — its ``"kernel"`` key selects the fused paged-attention decode).
    ``n_tok`` (verify mode only) is the per-slot valid window length; it
    is passed through conditionally so mixers that never see verify mode
    (SSM — excluded by the speculatable gate) keep their signature."""
    _, _, apply_fn = _mixer(spec)
    kw = {}
    if n_tok is not None:
        kw["n_tok"] = n_tok
    out, new_cache = apply_fn(x, p["mixer"], cfg, spec, mode=mode, pos=pos,
                              cache=cache, cache_len=cache_len, pages=pages,
                              attn_extent=attn_extent, **kw)
    if mode in ("decode", "prefill_chunk", "verify"):
        # donation contract: cache-updating modes keep every leaf's
        # shape/dtype, so the serve jits can alias donated buffers
        check_cache_invariant(cache, new_cache, f"{spec.kind}/{spec.attn}")
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if spec.mlp != "none":
        with jax.named_scope("mlp"):
            xn = rms_norm(x, p["mlp"]["ln"], cfg.norm_eps)
            if spec.mlp == "dense":
                y = mlp_dense(xn, p["mlp"], cfg)
            else:
                fn = moe_mlp_ragged if cfg.moe_impl == "ragged" else moe_mlp
                y, aux = fn(xn, p["mlp"], cfg)
        x = x + y
    return x, new_cache, aux
