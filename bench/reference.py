"""Plain float32 reference of the served models, written from the
published descriptions in straightforward ``jax.numpy``: no kernels, no
cache, no batching.  It imports nothing of the program and regenerates
the weights from the seed (``weights.py``), one layer at a time, so a
full-width model fits beside its activations.

The same forward, with every matrix product's two operands rounded to
float8 (e4m3, one scale per tensor), is the control: the next precision
below the served bfloat16.

Departures from the published models, shared with the program:
MiniCPM3's ``scale_emb``/``scale_depth``/``dim_model_base`` multipliers
and its LongRoPE factors are left out, and every model uses plain RoPE
at the configuration's ``rope_theta``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import global_leaf, layer_leaf, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
QBLOCK = 512                 # query rows per attention block
NEG_INF = -1e30


def q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, low):
    if low:
        a, b = q8(a), q8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _einsum(spec, a, b, low):
    if low:
        a, b = q8(a), q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate-half RoPE; x: (T, H, D), pos: (T,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, scale, low):
    """Causal attention in blocks of QBLOCK queries; q/k: (T, H, Dq),
    v: (T, H, Dv); T is a multiple of QBLOCK."""
    t, h, _ = q.shape
    nb = t // QBLOCK
    keys = jnp.arange(t)

    def block(args):
        i, qi = args
        s = _einsum("qhd,khd->hqk", qi, k, low) * scale
        ok = keys[None, :] <= (i * QBLOCK + jnp.arange(QBLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, NEG_INF), axis=-1)
        return _einsum("hqk,khd->qhd", p, v, low)

    out = jax.lax.map(block, (jnp.arange(nb),
                              q.reshape(nb, QBLOCK, h, q.shape[-1])))
    return out.reshape(t, h, v.shape[-1])


class Model:
    """Widths of one configuration file, in the published key names."""

    def __init__(self, c: dict):
        self.attention = c["attention"]
        self.d = c["hidden_size"]
        self.layers = c["num_hidden_layers"]
        self.heads = c["num_attention_heads"]
        self.kv_heads = c["num_key_value_heads"]
        self.ff = c["intermediate_size"]
        self.vocab = c["vocab_size"]
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.bias = bool(c.get("attention_bias", False))
        self.tied = bool(c.get("tie_word_embeddings", False))
        self.head_dim = c.get("head_dim", self.d // self.heads)
        self.q_rank = c.get("q_lora_rank", 0)
        self.kv_rank = c.get("kv_lora_rank", 0)
        self.nope = c.get("qk_nope_head_dim", 0)
        self.rope_dim = c.get("qk_rope_head_dim", 0)
        self.v_dim = c.get("v_head_dim", 0)


def _w(key, path, layer, shape):
    """A served (bfloat16) weight, widened to float32."""
    return layer_leaf(key, path, layer, shape).astype(
        jnp.bfloat16).astype(jnp.float32)


def _mlp(x, key, l, m: Model, low):
    p = "blocks/0/mlp/"
    xn = rms(x, _w(key, p + "ln", l, (m.d,)), m.eps)
    g = _mm(xn, _w(key, p + "w_gate", l, (m.d, m.ff)), low)
    u = _mm(xn, _w(key, p + "w_up", l, (m.d, m.ff)), low)
    return _mm(jax.nn.silu(g) * u, _w(key, p + "w_down", l, (m.ff, m.d)),
               low)


def _gqa(x, key, l, m: Model, low):
    p = "blocks/0/mixer/"
    t = x.shape[0]
    h, hkv, dh = m.heads, m.kv_heads, m.head_dim
    xn = rms(x, _w(key, p + "ln", l, (m.d,)), m.eps)
    q = _mm(xn, _w(key, p + "wq", l, (m.d, h * dh)), low)
    k = _mm(xn, _w(key, p + "wk", l, (m.d, hkv * dh)), low)
    v = _mm(xn, _w(key, p + "wv", l, (m.d, hkv * dh)), low)
    if m.bias:
        q = q + _w(key, p + "bq", l, (h * dh,))
        k = k + _w(key, p + "bk", l, (hkv * dh,))
        v = v + _w(key, p + "bv", l, (hkv * dh,))
    pos = jnp.arange(t)
    q = rope(q.reshape(t, h, dh), pos, m.theta)
    k = rope(k.reshape(t, hkv, dh), pos, m.theta)
    v = v.reshape(t, hkv, dh)
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    o = attend(q, k, v, dh ** -0.5, low)
    return _mm(o.reshape(t, h * dh), _w(key, p + "wo", l, (h * dh, m.d)),
               low)


def _mla(x, key, l, m: Model, low):
    """Multi-head latent attention in its published (non-absorbed) form."""
    p = "blocks/0/mixer/"
    t = x.shape[0]
    h, dn, dr, dv, rkv = m.heads, m.nope, m.rope_dim, m.v_dim, m.kv_rank
    xn = rms(x, _w(key, p + "ln", l, (m.d,)), m.eps)
    qa = rms(_mm(xn, _w(key, p + "wq_a", l, (m.d, m.q_rank)), low),
             _w(key, p + "q_ln", l, (m.q_rank,)), m.eps)
    q = _mm(qa, _w(key, p + "wq_b", l, (m.q_rank, h * (dn + dr))),
            low).reshape(t, h, dn + dr)
    kva = _mm(xn, _w(key, p + "wkv_a", l, (m.d, rkv + dr)), low)
    ckv = rms(kva[:, :rkv], _w(key, p + "kv_ln", l, (rkv,)), m.eps)
    pos = jnp.arange(t)
    q_rope = rope(q[..., dn:], pos, m.theta)
    k_rope = rope(kva[:, None, rkv:], pos, m.theta)
    k_nope = _mm(ckv, _w(key, p + "wk_b", l, (rkv, h * dn)),
                 low).reshape(t, h, dn)
    v = _mm(ckv, _w(key, p + "wv_b", l, (rkv, h * dv)),
            low).reshape(t, h, dv)
    qf = jnp.concatenate([q[..., :dn], q_rope], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (t, h, dr))], -1)
    o = attend(qf, kf, v, (dn + dr) ** -0.5, low)
    return _mm(o.reshape(t, h * dv), _w(key, p + "wo", l, (h * dv, m.d)),
               low)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, key, l, m: Model, low: bool):
    mix = _mla if m.attention == "mla" else _gqa
    x = x + mix(x, key, l, m, low)
    return x + _mlp(x, key, l, m, low)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, rows, norm, head, m: Model, low: bool):
    xs = rms(x[rows], norm, m.eps)
    return _mm(xs, head, low)


class Reference:
    """Teacher-forced logits of a prompt followed by served tokens."""

    def __init__(self, config: dict, seed: int):
        self.m = Model(config)
        self.key = seed_key(seed)
        m = self.m
        k = self.key

        def widen(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        self.embed = jax.jit(lambda: widen(global_leaf(
            k, "embed/tok", (m.vocab, m.d))))()
        # a tied model's output head is its embedding table
        self.head = self.embed.T if m.tied else jax.jit(lambda: widen(
            global_leaf(k, "lm_head", (m.d, m.vocab))))()
        self.norm = jax.jit(lambda: widen(global_leaf(
            k, "final_norm", (m.d,))))()

    def logits(self, prompt, served, low: bool = False):
        """(n, vocab) float32 logits at the positions that chose each of
        the ``n`` served tokens."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        served = np.asarray(served, np.int32).reshape(-1)
        seq = np.concatenate([prompt, served[:-1]])
        t = len(seq)
        tp = -(-t // QBLOCK) * QBLOCK
        toks = np.zeros((tp,), np.int32)
        toks[:t] = seq
        x = self.embed[jnp.asarray(toks)]
        for l in range(self.m.layers):
            x = _layer(x, self.key, l, self.m, low)
        rows = jnp.arange(len(prompt) - 1, t)
        return _head(x, rows, self.norm, self.head, self.m, low)


def gaps(ref_logits, tokens):
    """Per position: the reference's best logit minus its logit for
    ``tokens`` (0 where the reference would have chosen it too)."""
    lg = np.asarray(ref_logits, np.float32)
    tok = np.asarray(tokens).reshape(-1)
    return lg.max(-1) - lg[np.arange(len(tok)), tok]
