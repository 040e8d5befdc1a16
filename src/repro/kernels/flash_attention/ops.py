"""jit'd public wrapper: (B, S, H, D) layout in, GQA-aware, ragged
lengths zero-padded to whole blocks, block sizes chosen from the shapes,
auto-interpret on non-TPU backends (validation mode)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bhsd

MAX_BLOCK = 512          # rows of a q or kv block
STEP_ROWS = 4096         # query rows (heads x block_q) one grid step runs


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def block_sizes(seq: int, heads: int, group: int) -> tuple[int, int]:
    """(block, heads per grid step) for a sequence of ``seq`` tokens and
    ``heads`` query heads in groups of ``group`` per kv head.

    Blocks are lane-aligned (multiples of 128) and at most ``MAX_BLOCK``
    rows, so a causal prompt wastes at most half a block per row of
    blocks on the masked side of the diagonal.  Heads are stacked into
    one grid step up to ``STEP_ROWS`` query rows, so a long prompt makes
    few steps and a short one still fills a step; a step's heads read
    whole kv heads (``group`` divides them or they divide ``group``)."""
    block = min(MAX_BLOCK, _round_up(seq, 128))
    per_step = max(n for n in range(1, heads + 1)
                   if heads % n == 0 and (group % n == 0 or n % group == 0)
                   and n * block <= STEP_ROWS)
    return block, per_step


def _pad_seq(x, target):
    if x.shape[1] == target:
        return x
    return jnp.pad(x, ((0, 0), (0, target - x.shape[1]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k", "heads",
                                             "clamp_kv", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=None, block_k=None, heads=None, clamp_kv=True,
                    interpret=None):
    """q: (B, Sq, H, D); k: (B, Sk, Hkv, D); v: (B, Sk, Hkv, Dv) ->
    (B, Sq, H, Dv).  Block sizes and heads per grid step left ``None``
    are chosen by :func:`block_sizes`."""
    if interpret is None:
        interpret = not _on_tpu()
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]
    block, per_step = block_sizes(max(sq, sk), h, h // hkv)
    block_q = block if block_q is None else min(block_q, _round_up(sq, 8))
    block_k = block if block_k is None else min(block_k, _round_up(sk, 8))
    heads = per_step if heads is None else heads
    sq_pad, sk_pad = _round_up(sq, block_q), _round_up(sk, block_k)
    qf = _pad_seq(q.transpose(0, 2, 1, 3).reshape(b * h, sq, d), sq_pad)
    kf = _pad_seq(k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d), sk_pad)
    vf = _pad_seq(v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, dv), sk_pad)
    of = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                              scale=scale, block_q=block_q, block_k=block_k,
                              heads=heads, kv_len=sk, clamp_kv=clamp_kv,
                              interpret=interpret)
    return of[:, :sq].reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
