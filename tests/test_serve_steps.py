"""Continuous-batching serve steps across frontends: slot insert/evict and
masked decode must reproduce the one-shot path's greedy tokens for plain
token LMs, ``vision_patches`` and ``audio_codebooks`` configs, and the
SWA/MoE and MLA attention families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models.lm import init_cache, init_params
from repro.serve import PagePool
from repro.steps import (chunkable, greedy_oneshot, init_paged_slot_cache,
                         init_slot_cache, make_batched_insert_step,
                         make_decode_step, make_insert_step,
                         make_prefill_chunk_step, make_prefill_step,
                         make_serve_step)

# whole-module: jit-compiles prefill/insert/decode per architecture —
# tier-1 only, not inner-loop
pytestmark = pytest.mark.slow

# plain GQA, SWA+MoE, MLA, vision frontend, audio frontend
ARCHS = ["qwen2.5-14b", "mixtral-8x7b", "minicpm3-4b", "internvl2-2b",
         "musicgen-large"]
# + attn/SSM/MoE hybrid for the paged fuzz (SSM state stays dense while
# the attention layer's K/V leaves page)
FUZZ_ARCHS = ARCHS + ["jamba-v0.1-52b"]
SLOTS, PLEN, GEN = 3, 8, 4
PAGE_SIZE = 4


@pytest.fixture(scope="module")
def built():
    return {}


def _build(arch, built):
    if arch not in built:
        cfg = get(arch).tiny()
        cache_len = PLEN + GEN + (
            cfg.n_patches if cfg.frontend == "vision_patches" else 0)
        params = init_params(cfg, jax.random.PRNGKey(0))
        shp = (SLOTS, PLEN) + ((cfg.n_codebooks,) if cfg.frontend ==
                               "audio_codebooks" else ())
        prompts = jax.random.randint(jax.random.PRNGKey(1), shp, 0,
                                     cfg.vocab)
        patches = None
        if cfg.frontend == "vision_patches":
            patches = jax.random.normal(
                jax.random.PRNGKey(2), (SLOTS, cfg.n_patches, cfg.d_model),
                jnp.float32) * 0.02
        can_chunk = chunkable(cfg, cache_len)
        built[arch] = dict(
            cfg=cfg, params=params, cache_len=cache_len, prompts=prompts,
            patches=patches,
            prefill=jax.jit(make_prefill_step(cfg, cache_len=cache_len)),
            serve=jax.jit(make_serve_step(cfg)),
            insert=jax.jit(make_insert_step(cfg)),
            decode=jax.jit(make_decode_step(cfg)),
            insert_dense=jax.jit(make_batched_insert_step(
                cfg, cache_len=cache_len, page_size=None)),
            insert_paged=jax.jit(make_batched_insert_step(
                cfg, cache_len=cache_len, page_size=PAGE_SIZE)),
            decode_paged=jax.jit(make_decode_step(
                cfg, cache_len=cache_len, page_size=PAGE_SIZE)),
            # fused-kernel leg: decode attention walks the block table
            # in-kernel instead of materialising the dense gather
            decode_paged_kernel=jax.jit(make_decode_step(
                cfg, cache_len=cache_len, page_size=PAGE_SIZE,
                paged_kernel=True)),
            chunk=(jax.jit(make_prefill_chunk_step(cfg,
                                                   cache_len=cache_len),
                           static_argnames=("attn_extent", "want_logits"))
                   if can_chunk else None),
            # donated legs: the steps consume the cache version they
            # rewrite (donate_argnums on the cache arg), exactly like
            # the engine's default fast path
            insert_dense_don=jax.jit(make_batched_insert_step(
                cfg, cache_len=cache_len, page_size=None),
                donate_argnums=(0,)),
            insert_paged_don=jax.jit(make_batched_insert_step(
                cfg, cache_len=cache_len, page_size=PAGE_SIZE),
                donate_argnums=(0,)),
            decode_don=jax.jit(make_decode_step(cfg),
                               donate_argnums=(1,)),
            decode_paged_don=jax.jit(make_decode_step(
                cfg, cache_len=cache_len, page_size=PAGE_SIZE),
                donate_argnums=(1,)),
            decode_paged_kernel_don=jax.jit(make_decode_step(
                cfg, cache_len=cache_len, page_size=PAGE_SIZE,
                paged_kernel=True), donate_argnums=(1,)),
            chunk_don=(jax.jit(make_prefill_chunk_step(
                cfg, cache_len=cache_len), donate_argnums=(1,),
                static_argnames=("attn_extent", "want_logits"))
                if can_chunk else None),
        )
    return built[arch]


def _oneshot_reference(b):
    """Batched prefill + scalar-pos decode (the pre-engine path)."""
    return np.asarray(greedy_oneshot(b["prefill"], b["serve"], b["params"],
                                     b["prompts"], b["patches"], GEN))


def _row_prefill(b, i):
    patches = b["patches"]
    rc, rl = b["prefill"](b["params"], b["prompts"][i:i + 1],
                          None if patches is None else patches[i:i + 1])
    return rc, jnp.argmax(rl, axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ARCHS)
def test_scrambled_insert_matches_oneshot(arch, built):
    """Insert rows in a scrambled slot order, decode fully active: every
    slot's greedy stream equals the one-shot batch's row."""
    b = _build(arch, built)
    cfg = b["cfg"]
    ref = _oneshot_reference(b)

    pool = init_slot_cache(cfg, SLOTS, b["cache_len"], jnp.dtype(cfg.dtype))
    extra = ((cfg.n_codebooks,) if cfg.frontend == "audio_codebooks"
             else ())
    toks = jnp.zeros((SLOTS, 1) + extra, jnp.int32)
    outs = {}
    for r in (2, 0, 1):                       # arrival != slot-id order
        rc, t0 = _row_prefill(b, r)
        pool = b["insert"](pool, rc, jnp.int32(r))
        toks = toks.at[r].set(t0[0])
        outs[r] = [np.asarray(t0)]
    active = jnp.ones((SLOTS,), bool)
    for _ in range(GEN - 1):
        toks, pool = b["decode"](b["params"], pool, toks, active)
        # force per tick, never accumulate lazy slices of rebound
        # arrays: this backend can recycle a buffer whose last Python
        # reference drops while a pending computation still reads it
        # (see examples/repro_buffer_lifetime.py) — the harness obeys
        # the same pinning/forcing discipline as the engine
        host = np.asarray(toks)
        for r in outs:
            outs[r].append(host[r:r + 1])
    got = np.concatenate(
        [np.concatenate(outs[r], axis=1) for r in range(SLOTS)], axis=0)
    assert np.array_equal(ref, got)


@pytest.mark.parametrize("arch", ARCHS)
def test_evict_and_reuse_slot_mid_decode(arch, built):
    """Slot churn: request A decodes alone (other slots dead), is evicted
    (mask off) when done, and its slot is reused by request B mid-stream —
    both streams must match the one-shot rows, and the dead slots'
    garbage must never leak into a live slot."""
    b = _build(arch, built)
    cfg = b["cfg"]
    ref = _oneshot_reference(b)

    pool = init_slot_cache(cfg, SLOTS, b["cache_len"], jnp.dtype(cfg.dtype))
    extra = ((cfg.n_codebooks,) if cfg.frontend == "audio_codebooks"
             else ())
    toks = jnp.zeros((SLOTS, 1) + extra, jnp.int32)
    active = np.zeros((SLOTS,), bool)

    # forcing discipline (matches the engine's): every decode tick is
    # forced to host before `active` mutates or `toks` is rebound
    # again — lazy slices of rebound arrays (and dropped jnp.array mask
    # temporaries) can read recycled buffers on this backend, see
    # examples/repro_buffer_lifetime.py

    # A = request 0 into slot 1; decodes 2 ticks alone
    rc, t0 = _row_prefill(b, 0)
    pool = b["insert"](pool, rc, jnp.int32(1))
    toks = toks.at[1].set(t0[0])
    active[1] = True
    out_a = [np.asarray(t0)]
    for _ in range(2):
        toks, pool = b["decode"](b["params"], pool, toks,
                                 jnp.array(active))
        out_a.append(np.asarray(toks)[1:2])

    # B = request 2 arrives into dead slot 0 while A keeps decoding
    rc, t0 = _row_prefill(b, 2)
    pool = b["insert"](pool, rc, jnp.int32(0))
    toks = toks.at[0].set(t0[0])
    active[0] = True
    out_b = [np.asarray(t0)]
    toks, pool = b["decode"](b["params"], pool, toks, jnp.array(active))
    host = np.asarray(toks)
    out_a.append(host[1:2])
    out_b.append(host[0:1])

    # A done (GEN tokens collected): evict, reuse its slot for request 1
    active[1] = False
    rc, t0 = _row_prefill(b, 1)
    pool = b["insert"](pool, rc, jnp.int32(1))
    toks = toks.at[1].set(t0[0])
    active[1] = True
    out_c = [np.asarray(t0)]
    for _ in range(GEN - 1):
        toks, pool = b["decode"](b["params"], pool, toks,
                                 jnp.array(active))
        host = np.asarray(toks)
        if len(out_b) < GEN:
            out_b.append(host[0:1])
            if len(out_b) == GEN:
                active[0] = False     # B done: evicted mid-stream
        out_c.append(host[1:2])

    got_a = np.concatenate(out_a, axis=1)[0]
    got_b = np.concatenate(out_b, axis=1)[0]
    got_c = np.concatenate(out_c, axis=1)[0]
    assert np.array_equal(got_a, ref[0])
    assert np.array_equal(got_b, ref[2])
    assert np.array_equal(got_c, ref[1])


def test_masked_decode_freezes_dead_slot_pos():
    """Dead slots emit token 0 and their pos does not advance."""
    b = _build("qwen2.5-14b", {})
    cfg = b["cfg"]
    pool = init_slot_cache(cfg, SLOTS, b["cache_len"], jnp.dtype(cfg.dtype))
    rc, t0 = _row_prefill(b, 0)
    pool = b["insert"](pool, rc, jnp.int32(2))
    toks = jnp.zeros((SLOTS, 1), jnp.int32).at[2].set(t0[0])
    active = jnp.asarray([False, False, True])
    pos0 = np.asarray(pool["pos"])
    toks, pool = b["decode"](b["params"], pool, toks, active)
    pos1 = np.asarray(pool["pos"])
    assert pos1[2] == pos0[2] + 1
    assert pos1[0] == pos0[0] and pos1[1] == pos0[1]
    assert int(toks[0, 0]) == 0 and int(toks[1, 0]) == 0


# ------------------------------------------------- dense/paged schedule fuzz
def _chunked_prefill_rows(b, chunk, chunk_fn=None):
    """Cache-append chunked prefill of the whole prompt batch (ragged last
    chunk; vision patches ride the first chunk; extent buckets + LM head
    skipped on non-final chunks, exactly like the engine's path).
    ``chunk_fn`` selects the jit (e.g. the donated variant, which
    consumes each version of the row cache exactly once — the chain
    below is single-owner by construction)."""
    cfg = b["cfg"]
    chunk_fn = chunk_fn or b["chunk"]
    rows = init_cache(cfg, SLOTS, b["cache_len"], jnp.dtype(cfg.dtype))
    npatch = cfg.n_patches if cfg.frontend == "vision_patches" else 0
    off = c0 = 0
    first = True
    logits = None
    pins = []      # slice/offset temporaries + displaced row versions
    while c0 < PLEN:
        c1 = min(c0 + chunk, PLEN)
        covered = off + (c1 - c0) + (npatch if first else 0)
        ext = min(b["cache_len"], -(-covered // chunk) * chunk)
        ct, od = b["prompts"][:, c0:c1], jnp.int32(off)
        pins.append((ct, od, rows))
        rows, logits = chunk_fn(b["params"], rows, ct, od,
                                b["patches"] if first else None,
                                attn_extent=ext, want_logits=c1 >= PLEN)
        off = covered
        first = False
        c0 = c1
    # drain the chunk chain before handing the rows out: every pinned
    # temporary and displaced (or donated) version has then executed,
    # so nothing pending can read a recycled buffer (the engine pins
    # and syncs per chunk the same way)
    jax.block_until_ready(rows["pos"])
    pins.clear()
    return rows, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _run_schedule(b, seed, page_size, insert, decode, n_req=6,
                  chunk=None, chunk_fn=None, check_alias=False):
    """Seeded schedule generator: requests (prompt rows reused mod SLOTS,
    fuzzed budgets) arrive in a random order into random free slots and
    decode ticks interleave randomly with inserts.  Paged
    (``page_size`` set): pages come from a deliberately tight PagePool
    (admission blocks on exhaustion) and are freed the tick a request
    completes.  Dense (``page_size=None``): same schedule on the per-slot
    layout.  Works with donated or copying jits — the cache is rebound
    on every step, so the single-owner discipline holds either way.
    Every request's greedy stream must equal its one-shot row prefix,
    bit for bit; ``check_alias`` additionally asserts the donated decode
    really reused the big cache leaf's buffer (the eliminated copy)."""
    cfg = b["cfg"]
    paged = page_size is not None
    ref = _oneshot_reference(b)
    rng = np.random.default_rng(seed)
    cache_len = b["cache_len"]
    npatch = cfg.n_patches if cfg.frontend == "vision_patches" else 0

    if chunk is not None:
        rows_cache, t0 = _chunked_prefill_rows(b, chunk, chunk_fn)
    else:
        rc, logits = b["prefill"](b["params"], b["prompts"], b["patches"])
        rows_cache, t0 = rc, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if paged:
        pps = cache_len // page_size
        # tight pool: enough for ~2 of 3 slots -> admission must block
        pool_pages = 2 * pps + 2
        pager = PagePool(pool_pages, page_size)
        cache = init_paged_slot_cache(cfg, SLOTS, cache_len,
                                      jnp.dtype(cfg.dtype), page_size,
                                      pool_pages)
        table = np.zeros((SLOTS, pps), np.int32)
    else:
        pager = table = None
        cache = init_slot_cache(cfg, SLOTS, cache_len,
                                jnp.dtype(cfg.dtype))
    extra = ((cfg.n_codebooks,) if cfg.frontend == "audio_codebooks"
             else ())
    toks = jnp.zeros((SLOTS, 1) + extra, jnp.int32)
    active = np.zeros((SLOTS,), bool)

    order = rng.permutation(n_req)
    gens = rng.integers(1, GEN + 1, n_req)
    waiting = list(order)
    live = {}                       # slot -> req id
    outs = {}
    pages_of = {}
    blocked_allocs = 0
    alias_checked = not check_alias
    # versioned pinning, harness edition: displaced cache/token versions
    # and mask/table temporaries stay referenced while dispatches may be
    # pending (same discipline as KVState; donated husks are harmless to
    # hold).  Dropped only after the final sync below.
    pins = []

    def free_slot_of(r, s):
        active[s] = False
        del live[s]
        if paged:
            table[s, :] = 0
            pager.free(pages_of.pop(r))

    for _ in range(10_000):
        if not waiting and not live:
            break
        free = np.flatnonzero(~active)
        want_insert = bool(waiting) and len(free) and \
            (not live or rng.random() < 0.5)
        did_insert = False
        if want_insert:
            i = int(waiting[0])
            row = i % SLOTS
            ids = None
            if paged:
                ids = pager.reserve(PLEN + npatch + int(gens[i]) - 1)
            if paged and ids is None:
                blocked_allocs += 1     # admission blocks; tick instead
            else:
                waiting.pop(0)
                s = int(rng.choice(free))
                pins.append((cache, toks))
                if paged:
                    pages_of[i] = ids
                    table[s, :] = 0
                    table[s, :len(ids)] = ids
                    trow = jnp.array(table[s])
                    pins.append(trow)
                    cache = insert(cache, rows_cache, jnp.int32(row),
                                   jnp.int32(s), trow)
                else:
                    cache = insert(cache, rows_cache, jnp.int32(row),
                                   jnp.int32(s))
                toks = toks.at[s].set(t0[row])
                outs[i] = [np.asarray(t0[row])]
                active[s] = True
                live[s] = i
                did_insert = True
                if len(outs[i]) >= gens[i]:
                    free_slot_of(i, s)
        if live and not did_insert:
            if not alias_checked:
                leaves = jax.tree.leaves(cache)
                big = max(leaves, key=lambda x: x.nbytes)
                big_ptr = big.unsafe_buffer_pointer()
            pins.append((cache, toks))
            args = (b["params"], cache, toks, jnp.array(active))
            if paged:
                args = args + (jnp.array(table),)
            pins.append(args[3:])
            toks, cache = decode(*args)
            if not alias_checked:
                new_ptrs = {x.unsafe_buffer_pointer()
                            for x in jax.tree.leaves(cache)}
                assert big_ptr in new_ptrs, (
                    "donated decode did not alias the big cache leaf — "
                    "the per-tick pool copy is back")
                alias_checked = True
            for s, i in list(live.items()):
                outs[i].append(np.asarray(toks[s]))
                if len(outs[i]) >= gens[i]:
                    free_slot_of(i, s)
    assert not waiting and not live, "schedule deadlocked"
    jax.block_until_ready(toks)
    pins.clear()                    # chain drained: nothing pending
    if paged:
        assert pager.used_pages == 0, "pages leaked"
    for i in range(n_req):
        got = np.concatenate(outs[i], axis=0)
        want = ref[i % SLOTS, :gens[i]]
        assert np.array_equal(got, want), (
            f"req {i} (row {i % SLOTS}, gen {gens[i]}, seed {seed})")
    return blocked_allocs


@pytest.mark.parametrize("arch", FUZZ_ARCHS)
@pytest.mark.parametrize("seed", [0, 1])
def test_paged_schedule_fuzz_matches_oneshot(arch, seed, built):
    """Fuzzed arrival order, slot churn, page alloc/free and (where exact)
    chunk boundaries: paged greedy streams == one-shot rows, bit for bit,
    across all five frontends plus the SSM hybrid."""
    b = _build(arch, built)
    chunk = None
    if b["chunk"] is not None:
        chunk = int(np.random.default_rng(100 + seed).choice([3, 5]))
    _run_schedule(b, seed, PAGE_SIZE, b["insert_paged"],
                  b["decode_paged"], chunk=chunk)


@pytest.mark.parametrize("arch", FUZZ_ARCHS)
@pytest.mark.parametrize("layout,donate", [("dense", False),
                                           ("dense", True),
                                           ("paged", True)])
def test_schedule_fuzz_donation_grid_matches_oneshot(arch, layout, donate,
                                                     built):
    """Donation on x off, dense x paged (the paged x off cell is the
    fuzz above), across plain/SWA+MoE/MLA/vision/audio frontends and the
    SSM hybrid: donated steps consume the cache version they rewrite —
    fuzzed schedules stay bit-identical to the one-shot rows, and (spot
    check) the donated decode really reuses the big cache leaf's
    buffer in place."""
    b = _build(arch, built)
    suffix = "_don" if donate else ""
    if layout == "paged":
        insert, decode = b["insert_paged" + suffix], \
            b["decode_paged" + suffix]
        ps = PAGE_SIZE
    else:
        insert, decode = b["insert_dense" + suffix], \
            b["decode" + suffix]
        ps = None
    chunk = chunk_fn = None
    if b["chunk"] is not None:
        chunk, chunk_fn = 3, (b["chunk_don"] if donate else b["chunk"])
    _run_schedule(b, 7, ps, insert, decode, chunk=chunk,
                  chunk_fn=chunk_fn,
                  check_alias=donate and arch == "qwen2.5-14b")


@pytest.mark.parametrize("arch", FUZZ_ARCHS)
@pytest.mark.parametrize("donate", [False, True])
def test_paged_kernel_schedule_fuzz_matches_oneshot(arch, donate, built):
    """The fused paged-attention kernel leg of the fuzz grid: the same
    seeded schedules (arrival order, slot churn, tight-pool admission,
    chunk boundaries) with decode attention reading K/V pages in place —
    greedy streams must stay bit-identical to the one-shot rows across
    all frontends plus the SSM hybrid, donated and copying alike."""
    b = _build(arch, built)
    suffix = "_don" if donate else ""
    chunk = chunk_fn = None
    if b["chunk"] is not None:
        chunk, chunk_fn = 3, (b["chunk_don"] if donate else b["chunk"])
    _run_schedule(b, 11 if donate else 4, PAGE_SIZE,
                  b["insert_paged" + suffix],
                  b["decode_paged_kernel" + suffix],
                  chunk=chunk, chunk_fn=chunk_fn,
                  check_alias=donate and arch == "qwen2.5-14b")


def test_paged_kernel_page_size_one_degenerate(built):
    """page_size=1 under the fused kernel: one token per page — the
    kernel grid runs one page per position and must still match."""
    b = _build("qwen2.5-14b", built)
    insert = jax.jit(make_batched_insert_step(
        b["cfg"], cache_len=b["cache_len"], page_size=1))
    decode = jax.jit(make_decode_step(
        b["cfg"], cache_len=b["cache_len"], page_size=1,
        paged_kernel=True))
    _run_schedule(b, 0, 1, insert, decode)


def test_paged_on_demand_growth_matches_oneshot(built):
    """On-demand paging at the steps level: insert binds only the pages
    the prompt needs (table tail at garbage page 0), and the tail is
    re-pointed at freshly-allocated pages just before ``pos`` crosses
    each boundary — sound because the decode scatter fills a page the
    moment its position range first goes live, so the greedy stream must
    equal the one-shot row exactly."""
    b = _build("qwen2.5-14b", built)
    ref = _oneshot_reference(b)
    cache_len = b["cache_len"]
    pps = cache_len // PAGE_SIZE
    pager = PagePool(pps + 2, PAGE_SIZE)
    cache = init_paged_slot_cache(b["cfg"], SLOTS, cache_len,
                                  jnp.dtype(b["cfg"].dtype), PAGE_SIZE,
                                  pps + 2)
    table = np.zeros((SLOTS, pps), np.int32)
    rc, t0 = _row_prefill(b, 0)
    held = pager.reserve(PLEN)              # prompt pages only
    assert len(held) < pps, "geometry must leave a garbage tail to grow"
    table[0, :len(held)] = held
    cache = b["insert_paged"](cache, rc, jnp.int32(0), jnp.int32(0),
                              jnp.array(table[0]))
    toks = jnp.zeros((SLOTS, 1), jnp.int32).at[0].set(t0[0])
    active = jnp.asarray([True, False, False])
    outs, pos, pins = [np.asarray(t0)], PLEN, []
    for _ in range(GEN - 1):
        while len(held) * PAGE_SIZE <= pos:     # grow before the tick
            got = pager.alloc(1)
            assert got is not None
            table[0, len(held)] = got[0]
            held += got
        td = jnp.array(table)
        pins.append((cache, toks, td))          # see _run_schedule
        toks, cache = b["decode_paged"](b["params"], cache, toks, active,
                                        td)
        outs.append(np.asarray(toks)[0:1])
        pos += 1
    pins.clear()                                # outs forced the chain
    assert len(held) > pager.pages_for(PLEN)    # growth actually fired
    got = np.concatenate(outs, axis=1)[0]
    assert np.array_equal(got, ref[0])
    pager.free(held)
    assert pager.used_pages == 0


def test_paged_admission_blocks_under_tight_pool(built):
    """The tight fuzz pool actually exercises exhaustion: across seeds at
    least one alloc must have been refused (and, per the fuzz asserts,
    refusal never corrupted a stream or leaked a page)."""
    b = _build("qwen2.5-14b", built)
    blocked = sum(_run_schedule(b, s, PAGE_SIZE, b["insert_paged"],
                                b["decode_paged"])
                  for s in range(4))
    assert blocked > 0


@pytest.mark.parametrize("donate", [False, True])
def test_paged_page_size_one_degenerate(donate, built):
    """page_size=1: one token per page, block table as long as the cache;
    still bit-identical — donated and copying alike."""
    b = _build("qwen2.5-14b", built)
    insert = jax.jit(make_batched_insert_step(
        b["cfg"], cache_len=b["cache_len"], page_size=1),
        donate_argnums=(0,) if donate else ())
    decode = jax.jit(make_decode_step(
        b["cfg"], cache_len=b["cache_len"], page_size=1),
        donate_argnums=(1,) if donate else ())
    _run_schedule(b, 0, 1, insert, decode, check_alias=donate)


@pytest.mark.parametrize("arch",
                         [a for a in FUZZ_ARCHS
                          if a not in ("mixtral-8x7b", "jamba-v0.1-52b")])
def test_chunked_prefill_rows_match_oneshot_prefill(arch, built):
    """Chunked prefill alone (ragged boundaries, patches on the first
    chunk): the appended row cache decodes exactly like the one-shot
    prefill's, for every chunk size including C=1 and C=PLEN."""
    b = _build(arch, built)
    ref = _oneshot_reference(b)
    for chunk in (1, 3, PLEN):
        rows, t0 = _chunked_prefill_rows(b, chunk)
        pool = init_slot_cache(b["cfg"], SLOTS, b["cache_len"],
                               jnp.dtype(b["cfg"].dtype))
        extra = ((b["cfg"].n_codebooks,)
                 if b["cfg"].frontend == "audio_codebooks" else ())
        toks = jnp.zeros((SLOTS, 1) + extra, jnp.int32)
        outs = []
        for r in range(SLOTS):
            pool = b["insert"](pool, {"pos": rows["pos"],
                                      "blocks": jax.tree.map(
                                          lambda x, rr=r: x[:, rr:rr + 1],
                                          rows["blocks"])},
                               jnp.int32(r))
            toks = toks.at[r].set(t0[r])
        outs = [t0]
        act = jnp.ones((SLOTS,), bool)
        for _ in range(GEN - 1):
            toks, pool = b["decode"](b["params"], pool, toks, act)
            outs.append(toks)
        got = np.asarray(jnp.concatenate(outs, axis=1))
        assert np.array_equal(got, ref), f"chunk={chunk}"


# ------------------------------------------------- verify step (spec decode)
# the speculation gate = chunkable (extent-invariant) non-audio configs:
# GQA, MLA and the vision frontend qualify; MoE capacity / SSM state /
# audio codebooks do not
SPEC_STEP_ARCHS = ["qwen2.5-14b", "minicpm3-4b", "internvl2-2b"]


def _verify_jit(b, page_size=None, donate=False):
    from repro.steps import make_verify_step

    key = f"verify_{page_size}_{donate}"
    if key not in b:
        b[key] = jax.jit(make_verify_step(b["cfg"],
                                          cache_len=b["cache_len"],
                                          page_size=page_size),
                         donate_argnums=(1,) if donate else ())
    return b[key]


def test_verify_step_requires_speculatable():
    """The gate: extent-bound configs (MoE capacity, SSM state) and the
    audio frontend (a step emits a codebook vector, not one id) cannot
    verify-append, and the step builder refuses them loudly."""
    from repro.steps import make_verify_step, speculatable

    for arch in ("mixtral-8x7b", "jamba-v0.1-52b", "musicgen-large"):
        cfg = get(arch).tiny()
        assert not speculatable(cfg, 16)
        with pytest.raises(AssertionError):
            make_verify_step(cfg, cache_len=16)
    assert speculatable(get("qwen2.5-14b").tiny(), 16)


@pytest.mark.parametrize("arch", SPEC_STEP_ARCHS)
def test_verify_s1_ticks_equal_decode_ticks(arch, built):
    """S=1 verify ticks (nobody drafted) *are* decode ticks — same
    einsum formulation, host-authoritative pos: driving the whole pool
    to completion through the verify jit alone must reproduce the
    one-shot reference rows bit for bit."""
    b = _build(arch, built)
    cfg = b["cfg"]
    ref = _oneshot_reference(b)
    verify = _verify_jit(b)
    pool = init_slot_cache(cfg, SLOTS, b["cache_len"], jnp.dtype(cfg.dtype))
    pos = np.zeros((SLOTS,), np.int32)
    toks = np.zeros((SLOTS, 1), np.int32)
    for r in range(SLOTS):
        rc, t0 = _row_prefill(b, r)
        pool = b["insert"](pool, rc, jnp.int32(r))
        pos[r] = int(np.asarray(rc["pos"]).reshape(-1)[0])
        toks[r, 0] = int(np.asarray(t0)[0, 0])
    outs = [toks.copy()]
    n_tok = np.ones((SLOTS,), np.int32)
    for _ in range(GEN - 1):
        nxt, pool = verify(b["params"], pool, jnp.array(toks),
                           jnp.array(pos), jnp.array(n_tok))
        toks = np.asarray(nxt)[:, :1].astype(np.int32)
        outs.append(toks.copy())
        pos += 1
    got = np.concatenate(outs, axis=1)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("arch", SPEC_STEP_ARCHS)
@pytest.mark.parametrize("layout,donate", [("dense", False),
                                           ("dense", True),
                                           ("paged", False)])
def test_verify_window_scores_the_decode_stream(arch, layout, donate,
                                                built):
    """One verify dispatch over a drafted window: lane i's argmax is the
    token the tick-by-tick run emits at position i+1.  Perfect drafts →
    every lane agrees (a full-window commit); a corrupted draft lane
    leaves the lanes before it byte-identical (the committed prefix) and
    its stale cache writes are overwritten by the next window
    (rollback-for-free) — asserted by re-running the perfect window on
    the same cache afterwards.  Dead slots stay masked (n_tok=0); the
    paged leg uses page_size=1 so the window crosses a page boundary at
    every position."""
    b = _build(arch, built)
    cfg = b["cfg"]
    ref = _oneshot_reference(b)
    k = GEN - 2             # drafts; S = k+1 lanes score ref[:, 1:GEN]
    if layout == "paged":
        ps = 1
        num_pages = b["cache_len"] + 2
        verify = _verify_jit(b, page_size=ps, donate=donate)
        insert = jax.jit(make_batched_insert_step(
            cfg, cache_len=b["cache_len"], page_size=ps))
        pool = init_paged_slot_cache(cfg, SLOTS, b["cache_len"],
                                     jnp.dtype(cfg.dtype), ps, num_pages)
        table = np.zeros((SLOTS, b["cache_len"]), np.int32)
    else:
        verify = _verify_jit(b, donate=donate)
        pool = init_slot_cache(cfg, SLOTS, b["cache_len"],
                               jnp.dtype(cfg.dtype))
        table = None
    rc, t0 = _row_prefill(b, 0)
    p0 = int(np.asarray(rc["pos"]).reshape(-1)[0])
    if layout == "paged":
        # bind every page the window can write (p0 .. p0+k), 1 token each
        table[0, :p0 + k + 1] = np.arange(1, p0 + k + 2)
        pool = insert(pool, rc, jnp.int32(0), jnp.int32(0),
                      jnp.array(table[0]))
    else:
        pool = b["insert"](pool, rc, jnp.int32(0))
    pos = jnp.array(np.array([p0, 0, 0], np.int32))
    n_tok = jnp.array(np.array([k + 1, 0, 0], np.int32))

    def window(draft):
        toks = np.zeros((SLOTS, k + 1), np.int32)
        toks[0, 0] = ref[0, 0]
        toks[0, 1:] = draft
        return jnp.array(toks)

    def dispatch(toks, cache):
        args = (b["params"], cache, toks, pos, n_tok)
        if layout == "paged":
            args = args + (jnp.array(table),)
        return verify(*args)

    # perfect drafts: the stream's own next tokens — every lane agrees
    nxt, pool = dispatch(window(ref[0, 1:1 + k]), pool)
    assert np.array_equal(np.asarray(nxt)[0], ref[0, 1:]), (
        "perfect-draft window disagreed with the tick-by-tick stream")

    # corrupt the last draft lane: the committed prefix (lanes before
    # it) must stay byte-identical — lane k-1's scores never see lane
    # k's token (causal masking inside the window)
    bad = window(ref[0, 1:1 + k])
    bad = bad.at[0, k].set((int(ref[0, k]) + 1) % cfg.vocab)
    nxt, pool = dispatch(bad, pool)
    assert np.array_equal(np.asarray(nxt)[0, :k], ref[0, 1:1 + k]), (
        "a rejected draft lane changed the lanes before it")

    # the corrupted run left stale KV past the committed extent: the
    # next window overwrites it position-for-position, so a re-run of
    # the perfect window must still agree on every lane
    nxt, pool = dispatch(window(ref[0, 1:1 + k]), pool)
    assert np.array_equal(np.asarray(nxt)[0], ref[0, 1:]), (
        "stale rejected-draft KV leaked into a later verify window")


# ------------------------------------------------- prefix-cache gather step
@pytest.mark.parametrize("arch",
                         ["qwen2.5-14b", "minicpm3-4b", "musicgen-large"])
@pytest.mark.parametrize("m_tokens", [PAGE_SIZE, PAGE_SIZE + 1])
def test_prefix_gather_plus_tail_chunk_matches_cold_prefill(arch, m_tokens,
                                                            built):
    """The prefix-cache hit path at the step level: insert a cold
    prefill's pages into the paged pool, gather the matched prefix back
    into a fresh B=1 row cache (full pages, and — at a mid-page offset —
    the copy-on-write fork page), chunk-prefill only the tail, and the
    resulting row must emit the cold row's greedy stream bit-for-bit."""
    from repro.steps import make_prefix_gather_step

    b = _build(arch, built)
    cfg = b["cfg"]
    ref = _oneshot_reference(b)
    cache_len, ps = b["cache_len"], PAGE_SIZE
    pps = cache_len // ps
    num_pages = SLOTS * pps + 1

    # cold leg: prefill row 0 one-shot, insert into the paged pool
    pager = PagePool(num_pages, ps)
    pool = init_paged_slot_cache(cfg, SLOTS, cache_len,
                                 jnp.dtype(cfg.dtype), ps, num_pages)
    rc, t0 = _row_prefill(b, 0)
    ids = pager.alloc(pager.pages_for(PLEN))
    trow_full = np.zeros((pps,), np.int32)
    trow_full[:len(ids)] = ids
    pool = b["insert_paged"](pool, rc, jnp.int32(0), jnp.int32(0),
                            jnp.array(trow_full))

    # warm leg: gather the "matched" prefix (m_tokens of it — the page
    # holding token m_tokens is the fork source when mid-page), then
    # chunk-prefill the tail [m_tokens, PLEN)
    gather = jax.jit(make_prefix_gather_step(cfg, cache_len=cache_len,
                                             page_size=ps))
    n_gather = -(-m_tokens // ps)               # full pages + fork page
    trow = np.zeros((pps,), np.int32)
    trow[:n_gather] = ids[:n_gather]
    rows = gather(pool, jnp.array(trow), jnp.int32(m_tokens))
    assert int(rows["pos"]) == m_tokens
    tail = b["prompts"][0:1, m_tokens:]
    rows, logits = b["chunk"](b["params"], rows, tail,
                              jnp.int32(m_tokens), None,
                              attn_extent=cache_len, want_logits=True)
    t_warm = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert np.array_equal(np.asarray(t_warm), np.asarray(t0)), (
        "warm prefill token != cold prefill token")

    # both rows must decode identically from here
    tok, cache = t_warm, rows
    outs = [np.asarray(tok)]
    for _ in range(GEN - 1):
        tok, cache = b["serve"](b["params"], cache, tok)
        outs.append(np.asarray(tok))
    got = np.concatenate(outs, axis=1)[0]
    assert np.array_equal(got, ref[0]), (
        f"warm stream diverged (m_tokens={m_tokens})")


# ------------------------------------------- paged pools ride the layer scan
def _pool_shapes(cache):
    """HLO shape strings of the stacked paged pool leaves, and their
    bytes."""
    leaves = [l for blk in cache["blocks"] for n, l in blk.items()
              if n in ("k", "v", "ckv", "krope")]
    stacked = {",".join(map(str, l.shape)) for l in leaves}
    return stacked, sum(l.size * l.dtype.itemsize for l in leaves)


@pytest.mark.parametrize("arch", ["minicpm3-4b", "qwen2.5-14b"])
@pytest.mark.parametrize("step", ["decode", "verify"])
def test_decode_step_does_not_copy_the_pool(arch, step):
    """The donated paged decode and verify steps update the stacked pool
    in place: the layer scan carries it whole and each layer scatters and
    gathers at its own index, so the compiled step holds no copy, slice
    or write-back of the stacked pool, and its temporaries stay below
    the pool's bytes."""
    import re

    from repro.models.lm import meta_shape_structs, param_meta
    from repro.steps import make_verify_step

    cfg = get(arch).tiny(n_layers=8)
    slots, cache_len, ps, pages = 16, 256, 8, 513
    dt = jnp.dtype(cfg.dtype)
    params = meta_shape_structs(param_meta(cfg), dt)
    cache = jax.eval_shape(lambda: init_paged_slot_cache(
        cfg, slots, cache_len, dt, ps, pages))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    table = i32(slots, cache_len // ps)
    if step == "decode":
        fn = make_decode_step(cfg, cache_len=cache_len, page_size=ps)
        args = (params, cache, i32(slots, 1),
                jax.ShapeDtypeStruct((slots,), bool), table)
    else:
        fn = make_verify_step(cfg, cache_len=cache_len, page_size=ps)
        args = (params, cache, i32(slots, 3), i32(slots), i32(slots), table)
    comp = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    stacked, pool_bytes = _pool_shapes(cache)
    bad = []
    for ln in comp.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     ln)
        if m and m.group(1) in stacked and m.group(2) in (
                "copy", "dynamic-slice", "dynamic-update-slice"):
            bad.append(ln.strip()[:120])
    assert not bad, bad
    temp = comp.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, (temp, pool_bytes)


@pytest.mark.parametrize("arch", ["minicpm3-4b", "qwen2.5-14b",
                                  "jamba-v0.1-52b"])
def test_paged_rows_land_at_their_layer(arch):
    """Three pattern repeats, scrambled page tables: two paged decode
    ticks leave every layer's pool holding exactly the rows the dense
    slot cache holds at that layer (and the hybrid's scanned SSM leaves
    equal the dense ones), with distinct content per layer — a row
    written at the wrong layer index, or read from one, would show."""
    cfg = get(arch).tiny(n_layers=3 * len(get(arch).pattern))
    cache_len, ps = PLEN + GEN, PAGE_SIZE
    pps = cache_len // ps
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (SLOTS, PLEN), 0,
                                 cfg.vocab)
    rows, logits = jax.jit(make_prefill_step(cfg, cache_len=cache_len))(
        params, prompts, None)
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    dt = jnp.dtype(cfg.dtype)
    num_pages = SLOTS * pps + 1
    table = np.random.default_rng(3).permutation(
        np.arange(1, num_pages)).reshape(SLOTS, pps).astype(np.int32)
    dense = init_slot_cache(cfg, SLOTS, cache_len, dt)
    paged = init_paged_slot_cache(cfg, SLOTS, cache_len, dt, ps, num_pages)
    ins_d = jax.jit(make_batched_insert_step(cfg, cache_len=cache_len))
    ins_p = jax.jit(make_batched_insert_step(cfg, cache_len=cache_len,
                                             page_size=ps))
    for s in range(SLOTS):
        dense = ins_d(dense, rows, jnp.int32(s), jnp.int32(s))
        paged = ins_p(paged, rows, jnp.int32(s), jnp.int32(s),
                      jnp.array(table[s]))
    dec_d = jax.jit(make_decode_step(cfg))
    dec_p = jax.jit(make_decode_step(cfg, cache_len=cache_len,
                                     page_size=ps))
    active = jnp.ones((SLOTS,), bool)
    td = tp = toks
    for _ in range(2):
        td, dense = dec_d(params, dense, td, active)
        tp, paged = dec_p(params, paged, tp, active, jnp.array(table))
        assert np.array_equal(np.asarray(td), np.asarray(tp))
    assert np.array_equal(np.asarray(dense["pos"]), np.asarray(paged["pos"]))
    written = PLEN + 2
    n_paged = 0
    for spec, bd, bp in zip(cfg.pattern, dense["blocks"], paged["blocks"]):
        for name, d in bd.items():
            d, p = np.asarray(d), np.asarray(bp[name])
            if p.shape == d.shape:               # scanned per layer
                assert np.array_equal(p, d), name
                continue
            n_paged += 1
            view = p[:, table].reshape(d.shape)  # (rep, slots, len, ...)
            assert np.array_equal(view[:, :, :written], d[:, :, :written]), (
                spec, name)
            assert not np.array_equal(d[0, :, :written], d[1, :, :written])
            assert not np.array_equal(d[1, :, :written], d[2, :, :written])
    assert n_paged >= 2
