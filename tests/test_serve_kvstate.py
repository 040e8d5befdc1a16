"""KVState: single-owner cache pytree, versioned pinning, and the
donation/pinning exclusivity invariant (a donated buffer must never also
be pinned).  Host-level + tiny-jit tests — inner-loop fast."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get
from repro.serve import GARBAGE_PAGE, KVState, alias_safe


CFG = get("qwen2.5-14b").tiny()
SLOTS, CACHE_LEN, PS = 3, 12, 4


def _kv(**kw):
    return KVState(CFG, SLOTS, CACHE_LEN, jnp.dtype(CFG.dtype),
                   page_size=PS, **kw)


_touch = jax.jit(lambda c: jax.tree.map(lambda x: x * 1, c))
_touch_don = jax.jit(lambda c: jax.tree.map(lambda x: x * 1, c),
                     donate_argnums=(0,))


# ------------------------------------------------------------- ownership
def test_donated_commit_never_pins_the_consumed_version():
    kv = _kv()
    kv.debug_validate = True
    v0 = kv.cache
    kv.commit(_touch_don(v0))
    assert kv.version == 1
    assert kv.pins == 0                  # v0 was consumed, not pinned
    # the donated version really is dead — single ownership, not style
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(v0))
    # and the invariant check would catch anyone pinning the husk
    kv.pin(v0)
    with pytest.raises(AssertionError, match="donated"):
        kv.assert_no_deleted_pins()


def test_flush_cap_forces_one_sync_then_clears():
    kv = _kv(pin_max=4)
    for _ in range(4):
        kv.pin(jnp.zeros((2,)))
        kv.flush(synced=False)
    assert kv.pins == 4 and kv.pin_syncs == 0    # at the cap: kept
    kv.pin(jnp.zeros((2,)))
    kv.flush(synced=False)                       # past the cap: drained
    assert kv.pins == 0 and kv.pin_syncs == 1


# ------------------------------------------------------------ block table
def test_bind_and_release_slot_pages_roundtrip():
    kv = _kv()
    ids = kv.pager.reserve(CACHE_LEN)            # all 3 logical pages
    assert ids is not None and len(ids) == CACHE_LEN // PS
    row = kv.bind_slot_pages(1, ids)
    assert np.array_equal(np.asarray(row), ids)
    assert np.array_equal(np.asarray(kv.table_dev)[1], ids)
    assert kv.pins >= 1                          # displaced mirror pinned
    kv.release_slot_pages(1)
    kv.sync_table()
    assert (np.asarray(kv.table_dev)[1] == GARBAGE_PAGE).all()
    kv.pager.free(ids)
    assert kv.pager.used_pages == 0


def test_partial_reservation_leaves_garbage_tail():
    kv = _kv()
    ids = kv.pager.reserve(PS + 1)               # 2 of 3 logical pages
    row = np.asarray(kv.bind_slot_pages(0, ids))
    assert list(row[:2]) == ids and row[2] == GARBAGE_PAGE


def test_grow_slot_pages_extends_the_garbage_tail():
    """On-demand growth: new physical pages land exactly on the garbage
    tail, one sync_table batches the mirror refresh, and growing over a
    live entry is loud."""
    kv = _kv()
    ids = kv.pager.reserve(PS + 1)               # 2 of 3 logical pages
    kv.bind_slot_pages(0, ids)
    more = kv.pager.alloc(1)
    kv.grow_slot_pages(0, more, base=len(ids))
    assert (np.asarray(kv.table_dev)[0, 2]
            == GARBAGE_PAGE)                     # mirror not yet synced
    kv.sync_table()
    assert list(np.asarray(kv.table_dev)[0]) == ids + more
    with pytest.raises(AssertionError, match="live table entries"):
        kv.grow_slot_pages(0, kv.pager.alloc(1), base=0)
    with pytest.raises(AssertionError, match="logical pages"):
        kv.grow_slot_pages(0, [5], base=CACHE_LEN // PS)


def test_grow_slot_pages_multi_page_in_one_call():
    """A speculative verify window can cross several page boundaries in
    one tick (spec_k >= page_size): growth binds a multi-page batch in
    one call, contiguously on the garbage tail, one mirror sync."""
    kv = _kv()
    ids = kv.pager.reserve(1)                    # 1 of 3 logical pages
    kv.bind_slot_pages(2, ids)
    more = kv.pager.alloc(2)                     # the whole window's worth
    kv.grow_slot_pages(2, more, base=len(ids))
    kv.sync_table()
    assert list(np.asarray(kv.table_dev)[2]) == ids + more
    with pytest.raises(AssertionError, match="logical pages"):
        kv.grow_slot_pages(2, kv.pager.alloc(1), base=3)


# ------------------------------------------------------------ alias_safe
def test_alias_safe_accepts_shape_dtype_preserving_step():
    kv = _kv()
    out = jax.eval_shape(_touch, kv.cache)
    alias_safe(kv.cache, out, "touch")


def test_alias_safe_rejects_dtype_or_shape_drift():
    kv = _kv()
    promoted = jax.eval_shape(
        jax.jit(lambda c: jax.tree.map(
            lambda x: x.astype(jnp.float32) * 1.0, c)), kv.cache)
    with pytest.raises(AssertionError, match="donation"):
        alias_safe(kv.cache, promoted, "promoting-step")


def test_stats_report_versions_and_pool():
    kv = _kv()
    kv.commit(_touch_don(kv.cache))
    st = kv.stats()
    assert st["kv_version"] == 1
    assert kv.pins == 0 and st["kv_pins"] == 0
    assert st["pages_capacity"] == kv.pager.capacity
