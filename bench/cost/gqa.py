"""Operations and bytes of grouped-query attention models (Qwen2.5) from
the configuration's shapes, per device.  Under tensor parallelism every
matrix, bias, the vocabulary and the key/value heads split over the
chips; the norms are held whole on each."""
from __future__ import annotations

BYTES = 2                                   # bfloat16 weights and cache


def _per_layer(m):
    h, hkv, dh = m.heads, m.kv_heads, m.head_dim
    split = (m.d * h * dh + 2 * m.d * hkv * dh + h * dh * m.d
             + 3 * m.d * m.ff)
    bias = (h + 2 * hkv) * dh if m.bias else 0
    return split, bias, 2 * m.d


def weight_bytes(m, tp: int = 1) -> int:
    split, bias, norms = _per_layer(m)
    vocab = m.vocab * m.d // tp * (1 if getattr(m, "tied", False) else 2)
    return BYTES * (m.layers * ((split + bias) // tp + norms) + vocab + m.d)


def read_bytes(m, tp: int = 1) -> int:
    if getattr(m, "tied", False):
        return weight_bytes(m, tp)
    return weight_bytes(m, tp) - BYTES * m.vocab * m.d // tp


def kv_bytes_per_token(m, tp: int = 1) -> int:
    return 2 * m.kv_heads * m.head_dim * BYTES * m.layers // tp


def matmul_flops(m, tp: int = 1) -> float:
    split, _, _ = _per_layer(m)
    return 2.0 * (m.layers * split + m.d * m.vocab) / tp


def decode_attn_flops(m, positions, tp: int = 1) -> float:
    return 4.0 * m.layers * m.heads / tp * m.head_dim * positions


prefill_attn_flops = decode_attn_flops


def decode_step(m, tokens: float, positions: float, tp: int = 1):
    flops = tokens * matmul_flops(m, tp) + decode_attn_flops(m, positions,
                                                              tp)
    return flops, read_bytes(m, tp) + kv_bytes_per_token(m, tp) * positions
