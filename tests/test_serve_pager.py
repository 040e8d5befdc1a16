"""Adversarial PagePool unit tests (host-only, fast): exhaustion is
all-or-nothing, freed pages are reusable by any other slot, fragmentation
after heavy churn never corrupts the free list, and the page_size=1
degenerate config works.  Engine-level exhaustion/churn equivalence lives
in tests/test_serve_engine.py."""
import numpy as np
import pytest

from repro.configs import get
from repro.serve import GARBAGE_PAGE, PagePool, auto_page_size
from repro.steps import chunkable, paged_names


def test_alloc_never_hands_out_garbage_page():
    p = PagePool(num_pages=9, page_size=4)
    ids = p.alloc(8)
    assert ids is not None and len(ids) == 8
    assert GARBAGE_PAGE not in ids
    assert sorted(ids) == list(range(1, 9))
    assert p.free_pages == 0


def test_exhaustion_is_all_or_nothing():
    p = PagePool(num_pages=5, page_size=2)      # 4 usable
    a = p.alloc(3)
    assert a is not None
    # only 1 free: a request for 2 must get nothing, not a partial grant
    before = p.free_pages
    assert p.alloc(2) is None
    assert p.free_pages == before == 1
    assert p.alloc_failures == 1
    assert p.alloc(1) is not None               # exact fit still works


def test_free_then_realloc_reuses_pages_for_another_slot():
    p = PagePool(num_pages=4, page_size=1)      # 3 usable
    slot_a = p.alloc(3)
    assert p.alloc(1) is None                   # full
    p.free(slot_a)
    slot_b = p.alloc(3)
    assert sorted(slot_b) == sorted(slot_a)     # same physical pages
    assert p.used_pages == 3


def test_fragmentation_after_heavy_churn():
    rng = np.random.default_rng(0)
    p = PagePool(num_pages=33, page_size=2)     # 32 usable
    held = []
    for _ in range(500):
        if held and (rng.random() < 0.5 or p.free_pages < 4):
            p.free(held.pop(rng.integers(len(held))))
        else:
            got = p.alloc(int(rng.integers(1, 5)))
            if got is not None:
                held.append(got)
        # invariants under churn: no garbage page, no duplicates anywhere
        live = [i for ids in held for i in ids]
        assert GARBAGE_PAGE not in live
        assert len(live) == len(set(live))
        assert p.used_pages == len(live)
    # a large alloc spanning many non-contiguous freed regions still works
    for ids in held:
        p.free(ids)
    big = p.alloc(32)
    assert big is not None and len(set(big)) == 32


def test_page_size_one_degenerate_config():
    p = PagePool(num_pages=17, page_size=1)
    assert p.pages_for(13) == 13
    assert p.pages_for(1) == 1
    assert p.pages_for(0) == 0
    ids = p.alloc(16)
    assert ids is not None and p.alloc(1) is None
    p.free(ids[:7])
    assert p.free_pages == 7


def test_pages_for_rounds_up():
    p = PagePool(num_pages=9, page_size=4)
    assert p.pages_for(1) == 1
    assert p.pages_for(4) == 1
    assert p.pages_for(5) == 2
    assert p.pages_for(8) == 2
    assert p.pages_for(9) == 3


def test_stats_and_peak_tracking():
    p = PagePool(num_pages=9, page_size=4)
    a = p.alloc(5)
    p.free(a[:2])
    p.alloc(1)
    s = p.stats()
    assert s["pages_capacity"] == 8
    assert s["pages_used"] == 4
    assert s["pages_used_peak"] == 5
    assert s["page_allocs"] == 2


def test_double_free_and_bad_ids_are_loud():
    p = PagePool(num_pages=5, page_size=2)
    ids = p.alloc(2)
    p.free(ids)
    with pytest.raises(AssertionError):
        p.free(ids)                             # double free
    with pytest.raises(AssertionError):
        p.free([GARBAGE_PAGE])                  # garbage page
    with pytest.raises(AssertionError):
        p.free([99])                            # out of range


def test_auto_page_size_picks_largest_divisor():
    assert auto_page_size(64) == 8
    assert auto_page_size(14) == 7
    assert auto_page_size(12) == 6
    assert auto_page_size(13) == 1              # prime: degenerate
    assert auto_page_size(4) == 4


def test_paged_names_and_chunkable_predicates():
    qwen = get("qwen2.5-14b").tiny()
    mixtral = get("mixtral-8x7b").tiny()
    jamba = get("jamba-v0.1-52b").tiny()
    mla = get("minicpm3-4b").tiny()
    assert paged_names(qwen.pattern[0], 16) == {"k", "v"}
    assert paged_names(mla.pattern[0], 16) == {"ckv", "krope"}
    # mixtral tiny window (4096) >= cache_len: ring is linear -> paged
    assert paged_names(mixtral.pattern[0], 16) == {"k", "v"}
    # a true ring (window < cache_len) stays dense
    assert paged_names(mixtral.pattern[0], 8192) == frozenset()
    assert all(paged_names(s, 16) == frozenset() for s in jamba.pattern
               if s.kind == "ssm")
    assert chunkable(qwen, 16)
    assert chunkable(mla, 16)
    assert not chunkable(mixtral, 16)           # MoE routing is extent-bound
    assert not chunkable(jamba, 16)             # SSM chunk boundaries
    assert chunkable(get("internvl2-2b").tiny(), 20)
    assert chunkable(get("musicgen-large").tiny(), 16)


# ---------------------------------------------- refcounts + prefix sharing
def test_share_release_lifecycle_and_double_release_is_loud():
    p = PagePool(num_pages=5, page_size=2)
    ids = p.alloc(2)
    assert p.live_refs == 2 and p.shared_pages == 0
    p.share(ids)                                # second holder
    assert p.live_refs == 4 and p.shared_pages == 2
    p.release(ids)                              # first holder gone
    assert p.used_pages == 2                    # still held once
    p.release(ids)                              # last ref: freed
    assert p.used_pages == 0 and p.free_pages == 4
    with pytest.raises(AssertionError, match="double release"):
        p.release(ids)


def test_free_of_shared_or_cached_page_is_loud():
    p = PagePool(num_pages=5, page_size=2)
    ids = p.alloc(1)
    p.share(ids)
    with pytest.raises(AssertionError, match="shared"):
        p.free(ids)                             # two holders
    p.release(ids)
    cached = p.alloc(1)
    p.cache_pages(cached)
    with pytest.raises(AssertionError, match="cached"):
        p.free(cached)                          # trie owns it
    p.release(cached)                           # ref 0, stays allocated
    assert p.used_pages == 2 and p.cached_pages == 1
    assert p.uncache(cached) == 1               # withdrawn: freed now
    p.free(ids)
    assert p.used_pages == 0


def test_cached_page_survives_release_and_is_reshareable():
    p = PagePool(num_pages=4, page_size=1)
    ids = p.alloc(3)
    p.cache_pages(ids)
    p.release(ids)                              # slot done; trie keeps them
    assert p.used_pages == 3 and p.free_pages == 0 and p.live_refs == 0
    p.share(ids)                                # a hit re-holds them
    assert p.live_refs == 3
    p.release(ids)
    assert p.uncache(ids) == 3
    assert p.free_pages == 3


def test_share_of_unallocated_page_is_loud():
    p = PagePool(num_pages=5, page_size=2)
    ids = p.alloc(1)
    p.free(ids)
    with pytest.raises(AssertionError):
        p.share(ids)                            # free page: not shareable
    with pytest.raises(AssertionError):
        p.share([GARBAGE_PAGE])


def test_share_then_free_churn_keeps_pool_consistent():
    rng = np.random.default_rng(7)
    p = PagePool(num_pages=17, page_size=2)
    p.debug_validate = True                     # validate on every op
    holders: list = []                          # lists of page ids, 1 ref each
    for _ in range(600):
        r = rng.random()
        if holders and r < 0.35:
            p.release(holders.pop(rng.integers(len(holders))))
        elif holders and r < 0.6:
            ids = holders[rng.integers(len(holders))]
            p.share(ids)                        # alias an existing holding
            holders.append(list(ids))
        else:
            got = p.alloc(int(rng.integers(1, 4)))
            if got is not None:
                holders.append(got)
    for ids in holders:
        p.release(ids)
    assert p.used_pages == 0 and p.live_refs == 0
    p.debug_validate_now()


def _trie(num_pages=33, page_size=4):
    from repro.serve import PrefixCache
    p = PagePool(num_pages=num_pages, page_size=page_size)
    return p, PrefixCache(p, page_size)


def test_trie_match_full_pages_then_fork_into_divergent_page():
    p, t = _trie()
    toks = np.arange(12)
    ids = p.alloc(3)
    t.insert(toks, ids, 12)                     # 3 full pages cached
    p.release(ids)                              # inserting slot finished
    assert t.n_pages == 3 and p.cached_pages == 3
    # same 8-token prefix, diverges inside page 2 (1 matching token)
    q = toks.copy()
    q[9] = 99
    m = t.match_and_lock(q, len(q) - 1)
    assert m.pages == ids[:2] and m.full_tokens == 8
    assert m.fork_src == ids[2] and m.fork_len == 1 and m.tokens == 9
    assert all(p.refcount(i) == 1 for i in ids)     # one hold each
    t.release_fork(m)
    assert p.refcount(ids[2]) == 0
    t.release(m)
    assert p.live_refs == 0


def test_trie_match_is_capped_and_misses_cleanly():
    p, t = _trie()
    toks = np.arange(12)
    ids = p.alloc(3)
    t.insert(toks, ids, 12)
    p.release(ids)
    # cap at 11: pages 0-1 full, page 2 partial-matches 3 of 4 tokens
    m = t.match_and_lock(toks, len(toks) - 1)
    assert m.tokens == 11 and m.fork_len == 3
    t.release(m)
    # a prompt diverging at token 0 misses entirely (no holds taken)
    miss = t.match_and_lock(np.arange(100, 112), 11)
    assert miss.tokens == 0 and not miss.pages and miss.fork_src is None
    assert p.live_refs == 0


def test_trie_insert_is_first_wins():
    p, t = _trie()
    toks = np.arange(8)
    a, b = p.alloc(2), p.alloc(2)
    assert t.insert(toks, a, 8) == 2
    assert t.insert(toks, b, 8) == 0            # duplicate runs: kept out
    m = t.match_and_lock(np.concatenate([toks, [77]]), 8)
    assert m.pages == a                         # existing pages win
    t.release(m)
    assert p.cached_pages == 2                  # b stays private
    p.free(b)


def test_trie_lru_evicts_oldest_ref0_leaf_first():
    p, t = _trie()
    old, new = p.alloc(1), p.alloc(1)
    t.insert(np.arange(4), old, 4)
    t.insert(np.arange(50, 54), new, 4)
    # touch the first branch so the second becomes LRU
    m = t.match_and_lock(np.concatenate([np.arange(4), [9]]), 4)
    t.release(m)
    p.release(old + new)                        # both ref 0
    assert t.evict_lru(1) == 1
    assert p.is_cached(old[0]) and not p.is_cached(new[0])


def test_trie_lru_skips_held_pages_and_interior_nodes():
    p, t = _trie()
    ids = p.alloc(3)
    t.insert(np.arange(12), ids, 12)
    p.release(ids)
    m = t.match_and_lock(np.arange(12), 11)     # holds pages 0-1 + fork 2
    # every leaf-ward page is held: nothing reclaimable
    assert t.evict_lru(3) == 0
    t.release(m)
    # leaf-first: 3 evictions peel the chain leaf -> root, never interior
    free0 = p.free_pages
    assert t.evict_lru(1) == 1 and p.free_pages == free0 + 1
    assert t.evict_lru(2) == 2 and p.free_pages == free0 + 3
    assert t.n_pages == 0


def test_trie_page_size_one_degenerate():
    p, t = _trie(num_pages=9, page_size=1)
    ids = p.alloc(4)
    t.insert(np.asarray([5, 6, 7, 8]), ids, 4)
    m = t.match_and_lock(np.asarray([5, 6, 9]), 2)
    assert m.tokens == 2 and m.pages == ids[:2]
    assert m.fork_src is None                   # ps=1: no partial runs
    t.release(m)
    p.release(ids)
    assert t.evict_lru(9) == 4 and p.used_pages == 0


def test_trie_clear_uncaches_everything():
    p, t = _trie()
    ids = p.alloc(2)
    t.insert(np.arange(8), ids, 8)
    held = p.alloc(1)
    t.insert(np.arange(100, 104), held, 4)      # still ref 1: not freed yet
    p.release(ids)
    assert t.clear() == 2                       # ref-0 pages freed now
    assert p.used_pages == 1 and not p.is_cached(held[0])
    p.release(held)
    assert p.used_pages == 0


@pytest.mark.parametrize("walk", ["stats", "n_pages", "evict_lru"])
def test_trie_walks_a_chain_deeper_than_the_recursion_limit(walk):
    """An 8k context at page size 8 caches a 1,024-page chain; the trie's
    walks must not spend a Python frame per page (the default limit is
    1,000).  1,100 pages of size 1, under the default limit."""
    import sys

    n = 1_100
    p, t = _trie(num_pages=n + 1, page_size=1)
    ids = p.alloc(n)
    t.insert(np.arange(n), ids, n)
    p.release(ids)
    limit = sys.getrecursionlimit()
    sys.setrecursionlimit(1_000)
    try:
        if walk == "stats":
            assert t.stats()["prefix_nodes"] == n
        elif walk == "n_pages":
            assert t.n_pages == n
        else:
            # leaf first, each freed leaf exposing its parent: the chain
            assert t.evict_lru(n) == n
            assert p.used_pages == 0
    finally:
        sys.setrecursionlimit(limit)
    if walk == "evict_lru":
        assert t.n_pages == 0
