"""Operations and bytes of multi-head latent attention models (MiniCPM3,
DeepSeek-V2 style) from the configuration's shapes, per device.

Decode counts the absorbed form, the least work a decode step needs:
scores against the compressed latent (rank ``kv_lora_rank``) plus the
RoPE part, and the attended latent taken back through ``wv_b``.  Under
tensor parallelism heads, feed-forward and vocabulary split over the
chips; ``wq_a``, ``wkv_a``, the norms and the latent cache (no head
axis) are held whole on each.
"""
from __future__ import annotations

BYTES = 2                                   # bfloat16 weights and cache


def _per_layer(m):
    """(split, whole) parameters of one layer."""
    h, dn, dr, dv = m.heads, m.nope, m.rope_dim, m.v_dim
    split = (m.q_rank * h * (dn + dr) + m.kv_rank * h * dn
             + m.kv_rank * h * dv + h * dv * m.d + 3 * m.d * m.ff)
    whole = m.d * m.q_rank + m.d * (m.kv_rank + dr)
    norms = 2 * m.d + m.q_rank + m.kv_rank
    return split, whole, norms


def weight_bytes(m, tp: int = 1) -> int:
    """Bytes of every weight held on one device."""
    split, whole, norms = _per_layer(m)
    vocab = m.vocab * m.d // tp * (1 if getattr(m, "tied", False) else 2)
    return BYTES * (m.layers * (split // tp + whole + norms) + vocab + m.d)


def read_bytes(m, tp: int = 1) -> int:
    """Weight bytes one decode step must read on a device: every matrix
    it multiplies by (not the embedding table, only looked up)."""
    if getattr(m, "tied", False):
        return weight_bytes(m, tp)
    return weight_bytes(m, tp) - BYTES * m.vocab * m.d // tp


def kv_bytes_per_token(m, tp: int = 1) -> int:
    return (m.kv_rank + m.rope_dim) * BYTES * m.layers


def matmul_flops(m, tp: int = 1) -> float:
    """Matrix-product operations per token on one device, LM head in."""
    split, whole, _ = _per_layer(m)
    return 2.0 * (m.layers * (split / tp + whole) + m.d * m.vocab / tp)


def decode_attn_flops(m, positions, tp: int = 1) -> float:
    """Absorbed decode attention over ``positions`` cached positions."""
    per = 2 * (m.kv_rank + m.rope_dim) + 2 * m.kv_rank
    return float(m.layers * m.heads / tp * per) * positions


def prefill_attn_flops(m, positions, tp: int = 1) -> float:
    """Published (non-absorbed) attention of one prompt token over
    ``positions`` positions, plus rebuilding its key and value."""
    per = 2 * (m.nope + m.rope_dim) + 2 * m.v_dim
    return float(m.layers * m.heads / tp * per) * positions


def decode_step(m, tokens: float, positions: float, tp: int = 1):
    """(operations, bytes) of one decode step on one device: ``tokens``
    live rows reading ``positions`` cached positions between them."""
    flops = tokens * matmul_flops(m, tp) + decode_attn_flops(m, positions,
                                                              tp)
    return flops, read_bytes(m, tp) + kv_bytes_per_token(m, tp) * positions
