"""Model weights made from ``--seed``: the same values for the served
program and for the plain reference, which regenerates them itself.

Every value is an integer in [-2**15, 2**15) times a power of two (a
norm weight is 1 plus such a value), so each is exact in float32 and its
cast to the served type is the same whatever fusion computes it.  A
stacked layer leaf draws layer ``l`` from its own key, so the reference
can make one layer at a time.  This module imports nothing of the
program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORMS = frozenset({"ln", "q_ln", "kv_ln", "final_norm"})
OUTPUTS = frozenset({"wo", "w_down"})        # residual-branch outputs


def seed_key(seed: int):
    """Key for any whole-number seed (more than 32 bits allowed)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def values(key, shape, name: str):
    """float32 values of one leaf (or one layer of a stacked leaf)."""
    bits = jax.random.bits(key, tuple(shape), jnp.uint32)
    x = ((bits >> 16).astype(jnp.int32) - 32768).astype(jnp.float32)
    if name in NORMS:
        return 1.0 + x * 2.0 ** -19               # 1 +- 1/16
    return x * (2.0 ** -22 if name in OUTPUTS else 2.0 ** -20)


def layer_leaf(key, path: str, layer, shape):
    """Layer ``layer`` of the stacked leaf at ``path`` (float32)."""
    return values(jax.random.fold_in(leaf_key(key, path), layer), shape,
                  path.rsplit("/", 1)[-1])


def global_leaf(key, path: str, shape):
    """An unstacked leaf (embedding, head, final norm), float32."""
    return values(leaf_key(key, path), shape, path.rsplit("/", 1)[-1])


def path_str(path) -> str:
    """'blocks/0/mixer/wq_a' from a pytree key path."""
    out = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                out.append(str(getattr(k, attr)))
                break
    return "/".join(out)


def make_tree(seed: int, shapes, dtype, out_shardings=None):
    """The whole tree in one jitted call on the device: ``shapes`` is a
    pytree of ShapeDtypeStruct-likes whose paths name the leaves; leaves
    under ``blocks`` are stacked over layers on their leading axis."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [path_str(p) for p, _ in flat]
    dims = [tuple(s.shape) for _, s in flat]
    dt = jnp.dtype(dtype)

    def build(key):
        out = []
        for path, shape in zip(paths, dims):
            if path.startswith("blocks/"):
                out.append(jax.lax.map(
                    lambda l, p=path, s=shape[1:]: layer_leaf(
                        key, p, l, s).astype(dt),
                    jnp.arange(shape[0])))
            else:
                out.append(global_leaf(key, path, shape).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))
