"""ServeEngine: request-queue semantics (fast) and engine/one-shot greedy
token equivalence — paged KV cache, batched & chunked prefill, pool
exhaustion, EOS/stop early-exit — under randomized arrival orders and
slot churn (slow)."""
import threading
import time

import numpy as np
import pytest

from repro.serve import Request, RequestQueue


# ------------------------------------------------------- queue (no jit, fast)
def test_request_queue_fifo_and_close():
    q = RequestQueue()
    reqs = [Request(i, None) for i in range(3)]
    for r in reqs:
        q.put(r)
    q.close()
    assert [q.get().rid for _ in range(3)] == [0, 1, 2]
    assert q.get() is None
    assert q.get() is None              # stays drained
    with pytest.raises(RuntimeError):
        q.put(Request(9, None))


def test_request_queue_put_stamps_arrival():
    q = RequestQueue()
    r = Request(0, None)
    assert r.t_submit is None
    q.put(r)
    assert r.t_submit is not None


def test_request_queue_get_blocks_until_put():
    q = RequestQueue()
    got = []

    def consumer():
        got.append(q.get())

    th = threading.Thread(target=consumer)
    th.start()
    time.sleep(0.05)
    assert not got                      # blocked, nothing queued
    r = Request(7, None)
    q.put(r)
    th.join(2)
    assert got and got[0] is r


def test_request_queue_get_batch_coalesces_a_round():
    q = RequestQueue()
    for i in range(5):
        q.put(Request(i, None))
    assert [r.rid for r in q.get_batch(3)] == [0, 1, 2]
    assert [r.rid for r in q.get_batch(3)] == [3, 4]
    q.close()
    assert q.get_batch(3) is None       # closed + drained
    q2 = RequestQueue()
    for i in range(4):
        q2.put(Request(i, None))
    assert [r.rid for r in q2.get_batch()] == [0, 1, 2, 3]  # no cap


def test_request_stop_fields_validation():
    r = Request(0, None, eos_id=5, stop=[[1, 2], (3,)])
    assert r.needs_host_tokens and r.stop == [[1, 2], [3]]
    assert not Request(1, None).needs_host_tokens
    with pytest.raises(AssertionError):
        Request(2, None, stop=[[]])


# ------------------------------------------- multi-token stop scan (fast)
def test_hit_stop_scans_full_committed_window():
    """Regression: a >1-token commit (speculative-decode acceptance) can
    bury the EOS or a completed stop sequence *inside* the committed
    window.  `_hit_stop` must scan every newly committed position — not
    just the tail — and truncate `out_tokens` at the first match so the
    emitted stream stays a prefix of the tick-by-tick one."""
    from repro.serve import ServeEngine

    # EOS mid-window: tail check alone would sail past it
    r = Request(0, None, eos_id=7)
    r.out_tokens = [3, 7, 9, 4]          # one 4-token commit, EOS at [1]
    assert ServeEngine._hit_stop(r, n_new=4)
    assert r.out_tokens == [3, 7]        # truncated at first match

    # stop sequence completing mid-window, starting *before* the window
    r = Request(1, None, stop=[[5, 6]])
    r.out_tokens = [1, 5]                # committed on earlier ticks
    assert not ServeEngine._hit_stop(r, n_new=1)
    r.out_tokens += [6, 2, 8]            # 3-token commit; [5,6] ends at [2]
    assert ServeEngine._hit_stop(r, n_new=3)
    assert r.out_tokens == [1, 5, 6]

    # earliest of several matches wins (eos and stop both inside window)
    r = Request(2, None, eos_id=9, stop=[[4, 4]])
    r.out_tokens = [4, 4, 9, 1]
    assert ServeEngine._hit_stop(r, n_new=4)
    assert r.out_tokens == [4, 4]

    # single-token commits keep the old semantics exactly
    r = Request(3, None, eos_id=7)
    r.out_tokens = [7, 1, 2]             # stale eos outside the window
    assert not ServeEngine._hit_stop(r, n_new=1)
    r.out_tokens.append(7)
    assert ServeEngine._hit_stop(r, n_new=1)
    assert r.out_tokens == [7, 1, 2, 7]


# --------------------------------------------- drafting, host-only (fast)
def test_ngram_drafter_prompt_lookup():
    """Prompt lookup: the draft is the continuation of the most recent
    earlier occurrence of the stream's suffix n-gram — longest n-gram
    wins, most recent occurrence wins, no match means no draft."""
    from repro.serve.spec import NgramDrafter

    d = NgramDrafter()
    # suffix [7, 8] recurs at the start; its continuation there is [9, 1]
    assert d.draft([7, 8, 9, 1, 7, 8], 2) == [9, 1]
    # both the 2-gram [2, 3] and the 3-gram [1, 2, 3] recur: the longer
    # match picks the continuation [5], not [1]
    assert d.draft([1, 2, 3, 5, 9, 2, 3, 1, 2, 3], 1) == [5]
    # [4, 5] occurs twice earlier — the most recent one (-> [7]) wins
    assert d.draft([4, 5, 6, 4, 5, 7, 4, 5], 1) == [7]
    # fewer than k available past the match is legal
    assert d.draft([7, 8, 9, 7, 8], 5) == [9, 7, 8]
    # degenerate inputs: no context, no repeat, k == 0
    assert d.draft([], 3) == []
    assert d.draft([1, 2, 3], 2) == []
    assert d.draft([7, 8, 9, 7, 8], 0) == []


def test_make_drafter_parses_specs():
    from repro.serve.spec import Drafter, NgramDrafter, make_drafter

    d = make_drafter("ngram")
    assert isinstance(d, NgramDrafter)
    assert (d.max_ngram, d.min_ngram) == (3, 1)
    d = make_drafter("ngram:4,2")
    assert (d.max_ngram, d.min_ngram) == (4, 2)
    assert make_drafter("ngram:5").max_ngram == 5
    mine = NgramDrafter()
    assert make_drafter(mine) is mine           # instance passthrough
    with pytest.raises(ValueError, match="unknown drafter"):
        make_drafter("model")
    with pytest.raises(NotImplementedError):
        Drafter().draft([1, 2], 1)


def test_policy_abandons_speculation_per_request():
    """spec_draft_k: a request whose observed acceptance rate stays under
    spec_min_accept after the warmup budget gets no more drafts (its
    verify lanes are pure waste) — while a well-predicted request keeps
    the full window."""
    from types import SimpleNamespace

    from repro.serve import SchedulerPolicy

    pol = SchedulerPolicy()
    eng = SimpleNamespace(spec_k=4)
    cold = Request(0, None)
    assert (cold.spec_drafted, cold.spec_accepted) == (0, 0)
    assert pol.spec_draft_k(eng, cold) == 4     # warmup: always draft

    bad = Request(1, None)
    bad.spec_drafted, bad.spec_accepted = 20, 1     # 5% < 10% floor
    assert pol.spec_draft_k(eng, bad) == 0

    good = Request(2, None)
    good.spec_drafted, good.spec_accepted = 20, 10
    assert pol.spec_draft_k(eng, good) == 4

    # still inside the warmup budget: no abandonment yet
    young = Request(3, None)
    young.spec_drafted, young.spec_accepted = pol.spec_warmup - 1, 0
    assert pol.spec_draft_k(eng, young) == 4


# ------------------------------------------------- engine equivalence (slow)
N_REQ, PLEN, GEN_MAX = 8, 8, 6
CACHE_LEN = PLEN + GEN_MAX              # 14 -> auto page_size 7
PAGE_SIZE = 7


@pytest.fixture(scope="module")
def built():
    import jax
    import jax.numpy as jnp
    from repro.configs import get
    from repro.models.lm import init_params
    from repro.serve import make_jit_steps
    from repro.steps import greedy_oneshot, make_serve_step

    cfg = get("qwen2.5-14b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (N_REQ, PLEN), 0, cfg.vocab))
    steps = make_jit_steps(cfg, cache_len=CACHE_LEN, page_size=PAGE_SIZE,
                           chunk=True)
    serve_step = jax.jit(make_serve_step(cfg))

    # one-shot reference: all requests in one static batch
    ref = np.asarray(greedy_oneshot(steps["prefill"], serve_step, params,
                                    jnp.asarray(prompts), None, GEN_MAX))
    return dict(cfg=cfg, params=params, prompts=prompts, steps=steps,
                ref=ref)


def _run_engine(b, reqs, gaps=None, **kw):
    from repro.serve import ServeEngine

    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("umt", True)
    kw.setdefault("n_cores", 4)
    if kw.get("page_size", PAGE_SIZE) == PAGE_SIZE and \
            "jit_steps" not in kw:
        kw["jit_steps"] = b["steps"]
        kw.setdefault("page_size", PAGE_SIZE)
    with ServeEngine(b["cfg"], b["params"], **kw) as eng:
        for i, r in enumerate(reqs):
            eng.submit(r)
            if gaps is not None and gaps[i] > 0:
                time.sleep(gaps[i])
        eng.close()
        eng.join()
        stats = eng.stats()
        pager = eng.pager
    return stats, pager


@pytest.mark.slow
@pytest.mark.parametrize("seed,umt", [(0, True), (1, True), (2, False)])
def test_engine_matches_oneshot_under_random_arrivals(built, seed, umt):
    """Randomized arrival order, arrival gaps, and generation budgets over
    a 3-slot paged pool (slots < requests forces churn): every request's
    greedy tokens must equal its one-shot row, on the UMT runtime and
    baseline."""
    b = built
    rng = np.random.default_rng(seed)
    order = rng.permutation(N_REQ)
    gens = rng.integers(1, GEN_MAX + 1, N_REQ)  # incl. gen==1
    gaps = rng.exponential(0.005, N_REQ)

    reqs = [Request(int(i), b["prompts"][i], max_new_tokens=int(gens[i]))
            for i in order]
    stats, pager = _run_engine(b, reqs, gaps, umt=umt)

    for r in reqs:
        assert r.done.is_set()
        got = np.asarray(r.out_tokens, np.int32)
        assert got.shape == (r.max_new,)
        assert np.array_equal(got, b["ref"][r.rid, :r.max_new]), (
            f"request {r.rid} (seed {seed}, umt {umt})")
    assert stats["requests"] == N_REQ
    assert 0.0 < stats["occupancy"] <= 1.0
    assert stats["p50_latency_s"] <= stats["p99_latency_s"]
    assert stats["prefill_reqs"] == N_REQ
    # drained: no slot holds a ref; only trie-cached (refcount-0,
    # reclaimable) pages may remain allocated — idle reuse capital
    assert pager.live_refs == 0
    assert pager.used_pages == pager.cached_pages


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_engine_fuzz_pool_and_chunk_schedules(built, seed):
    """Seeded schedule fuzz at the engine level: random pool tightness
    (admission blocking), random chunked-prefill size (incl. ragged
    boundaries), random budgets/arrival gaps — tokens stay bit-identical
    to the one-shot rows and the pool drains clean."""
    b = built
    rng = np.random.default_rng(1000 + seed)
    pps = CACHE_LEN // PAGE_SIZE
    num_pages = int(rng.choice([2 * pps + 1, 2 * pps + 2, 3 * pps + 1]))
    chunk = rng.choice([0, 3, 5])       # 0 = unchunked
    gens = rng.integers(1, GEN_MAX + 1, N_REQ)
    gaps = rng.exponential(0.002, N_REQ)
    order = rng.permutation(N_REQ)

    reqs = [Request(int(i), b["prompts"][i], max_new_tokens=int(gens[i]))
            for i in order]
    stats, pager = _run_engine(
        b, reqs, gaps, num_pages=num_pages,
        prefill_chunk=int(chunk) if chunk else None)
    for r in reqs:
        got = np.asarray(r.wait(), np.int32)
        assert np.array_equal(got, b["ref"][r.rid, :r.max_new]), (
            f"request {r.rid} (seed {seed}, pages {num_pages}, "
            f"chunk {chunk})")
    if chunk:
        assert stats["prefill_chunks"] > 0
    assert pager.live_refs == 0
    assert pager.used_pages == pager.cached_pages
    assert stats["pages_used_peak"] <= pager.capacity


@pytest.mark.slow
def test_pool_exhaustion_serialises_but_never_corrupts(built):
    """A pool with room for exactly one request: admission must block
    (max one live slot, alloc failures observed) and every stream must
    still be bit-exact — exhaustion degrades throughput, never tokens."""
    b = built
    need = -(-(PLEN + GEN_MAX - 1) // PAGE_SIZE)    # pages per request
    reqs = [Request(i, b["prompts"][i], max_new_tokens=GEN_MAX)
            for i in range(5)]
    stats, pager = _run_engine(b, reqs, num_pages=need + 1)
    for r in reqs:
        assert np.array_equal(np.asarray(r.wait(), np.int32),
                              b["ref"][r.rid])
    assert stats["max_live_slots"] == 1
    assert pager.alloc_failures > 0
    assert pager.live_refs == 0
    assert pager.used_pages == pager.cached_pages
    # the policy-mechanism counters: each distinct blocked head counts
    # once, and the default worst-case policy never faults or preempts
    assert stats["admission_blocks"] > 0
    assert stats["policy"] == "reserve"
    assert stats["evictions"] == stats["restores"] \
        == stats["pages_grown"] == 0


@pytest.mark.slow
def test_eos_and_stop_sequences_evict_eagerly(built):
    """EOS / stop-sequence requests end the tick the pattern appears —
    output is the exact one-shot prefix including the stopping tokens —
    and their slot + pages free immediately (the pool is empty again as
    soon as the request completes, not at drain)."""
    from repro.serve import ServeEngine

    b = built
    ref = b["ref"]
    # eos at the 3rd emitted token of row 0; stop = rows 1's tokens 2..3
    eos = int(ref[0, 2])
    k_eos = int(np.argmax(ref[0] == eos)) + 1
    stop = [int(ref[1, 2]), int(ref[1, 3])]
    # find where that 2-gram first completes in row 1
    k_stop = next(j + 1 for j in range(1, GEN_MAX)
                  if list(ref[1, j - 1:j + 1]) == stop)
    r_eos = Request(0, b["prompts"][0], max_new_tokens=GEN_MAX,
                    eos_id=eos)
    r_stop = Request(1, b["prompts"][1], max_new_tokens=GEN_MAX,
                     stop=[stop])
    # eos on the very first (prefill) token: never takes a slot at all
    r_first = Request(2, b["prompts"][2], max_new_tokens=GEN_MAX,
                      eos_id=int(ref[2, 0]))
    with ServeEngine(b["cfg"], b["params"], slots=3, cache_len=CACHE_LEN,
                     umt=True, n_cores=4, jit_steps=b["steps"]) as eng:
        for r in (r_eos, r_stop, r_first):
            eng.submit(r)
            r.wait(timeout=60)
            assert r.done.is_set()
            # eager release: no slot holds a ref the moment the request
            # is done, while the engine is still up and idling (pages
            # the prefix trie cached stay allocated but reclaimable)
            assert eng.pager.live_refs == 0
        eng.close()
        eng.join()
        stats = eng.stats()
    assert np.array_equal(np.asarray(r_eos.wait(), np.int32),
                          ref[0, :k_eos])
    assert r_eos.stopped
    assert np.array_equal(np.asarray(r_stop.wait(), np.int32),
                          ref[1, :k_stop])
    assert r_stop.stopped
    assert np.array_equal(np.asarray(r_first.wait(), np.int32),
                          ref[2, :1])
    assert r_first.stopped and r_first.slot is None
    assert stats["stopped_early"] == 3


@pytest.mark.slow
def test_batched_prefill_coalesces_bursts(built):
    """A burst queued before start is prefilled in coalesced rounds (one
    batched call per round), not one call per request."""
    from repro.serve import ServeEngine

    b = built
    reqs = [Request(i, b["prompts"][i], max_new_tokens=3)
            for i in range(N_REQ)]
    eng = ServeEngine(b["cfg"], b["params"], slots=3, cache_len=CACHE_LEN,
                      umt=True, n_cores=4, jit_steps=b["steps"])
    for r in reqs:
        eng.submit(r)                   # whole burst queued before start
    with eng:
        eng.close()
        eng.join()
        stats = eng.stats()
    for r in reqs:
        assert np.array_equal(np.asarray(r.wait(), np.int32),
                              b["ref"][r.rid, :3])
    # 8 requests, rounds capped at slots=3 -> exactly ceil(8/3) calls
    assert stats["prefill_calls"] == 3
    assert stats["prefill_reqs"] == N_REQ


@pytest.mark.slow
def test_dense_legacy_engine_still_exact(built):
    """page_size == cache_len gives each slot one page: the one-shot
    tokens, one page per admitted request, every page back at the
    end."""
    from repro.serve import make_jit_steps

    b = built
    one = make_jit_steps(b["cfg"], cache_len=CACHE_LEN,
                         page_size=CACHE_LEN)
    reqs = [Request(i, b["prompts"][i], max_new_tokens=4)
            for i in range(5)]
    stats, pager = _run_engine(b, reqs, page_size=CACHE_LEN,
                               jit_steps=one)
    for r in reqs:
        assert np.array_equal(np.asarray(r.wait(), np.int32),
                              b["ref"][r.rid, :4])
    assert stats["page_size"] == CACHE_LEN
    assert pager.capacity == 3 and pager.live_refs == 0


@pytest.mark.parametrize("page_size", [None, 0, -7, 5])
def test_engine_page_size_must_be_a_positive_divisor(page_size):
    """Every engine is paged: no page size, a non-positive one or one
    that does not divide cache_len is refused before anything is
    built."""
    from repro.configs import get
    from repro.serve import ServeEngine

    with pytest.raises(ValueError, match="positive divisor"):
        ServeEngine(get("qwen2.5-14b").tiny(), {}, cache_len=CACHE_LEN,
                    page_size=page_size)


@pytest.mark.slow
def test_default_jit_steps_are_paged_at_the_engine_page_size(built):
    """``make_jit_steps`` with default arguments holds the engine's own
    layout rule: an engine handed them pages at ``auto_page_size`` and
    serves the one-shot tokens."""
    from repro.serve import ServeEngine, auto_page_size, make_jit_steps

    b = built
    steps = make_jit_steps(b["cfg"], cache_len=CACHE_LEN)
    assert steps["page_size"] == auto_page_size(CACHE_LEN)
    reqs = [Request(i, b["prompts"][i], max_new_tokens=3)
            for i in range(3)]
    with ServeEngine(b["cfg"], b["params"], slots=3, cache_len=CACHE_LEN,
                     jit_steps=steps) as eng:
        for r in reqs:
            eng.submit(r)
        eng.close()
        eng.join()
        assert eng.page_size == auto_page_size(CACHE_LEN)
        assert eng.kv.pages_per_slot == CACHE_LEN // eng.page_size
    for r in reqs:
        assert np.array_equal(np.asarray(r.wait(), np.int32),
                              b["ref"][r.rid, :3])


@pytest.mark.slow
def test_oversized_request_fails_loudly(built):
    """A request that cannot fit the pool cache fails its prefill; the
    failure lands on the request (wait re-raises) instead of returning an
    empty token list or hanging join() — and it cannot take down the
    valid requests coalesced into the same round."""
    b = built
    bad = Request(0, b["prompts"][0], max_new_tokens=CACHE_LEN)
    good = Request(1, b["prompts"][1], max_new_tokens=2)
    stats, pager = _run_engine(b, [bad, good], slots=2)
    assert bad.done.is_set() and bad.error is not None
    with pytest.raises(ValueError, match="exceeds cache_len"):
        bad.wait()
    assert np.array_equal(np.asarray(good.wait(), np.int32),
                          b["ref"][1, :2])
    assert pager.live_refs == 0
    assert pager.used_pages == pager.cached_pages


@pytest.mark.slow
def test_donated_version_never_pinned(built):
    """The donation/pinning exclusivity invariant, live: with
    debug_validate on, every commit scans the pin list for donated
    (deleted) buffers — a single overlap would throw inside the decode
    driver and fail the requests."""
    from repro.serve import ServeEngine

    b = built
    reqs = [Request(i, b["prompts"][i], max_new_tokens=GEN_MAX)
            for i in range(6)]
    eng = ServeEngine(b["cfg"], b["params"], slots=3, cache_len=CACHE_LEN,
                      umt=True, n_cores=4, jit_steps=b["steps"],
                      page_size=PAGE_SIZE)
    eng.kv.debug_validate = True
    with eng:
        for r in reqs:
            eng.submit(r)
        eng.close()
        eng.join()
        stats = eng.stats()
        eng.kv.assert_no_deleted_pins()
    for r in reqs:
        assert np.array_equal(np.asarray(r.wait(), np.int32),
                              b["ref"][r.rid])
    assert stats["kv_version"] > 0


@pytest.mark.slow
def test_chunked_prefill_runs_as_continuation_tasks(built):
    """Chunked prefill across rounds: every chunk is its own UMT task
    (re-enqueued continuation, not a loop inside one task), so two long
    rounds' chunks can interleave on a saturated pool.  Checked
    structurally on a traced runtime: one ``serve.prefill.chunk`` task
    start per chunk, and the chunk count matches the chunk arithmetic —
    with tokens still bit-exact."""
    from repro.core import UMTRuntime
    from repro.serve import ServeEngine

    b = built
    chunk = 3
    reqs = [Request(i, b["prompts"][i], max_new_tokens=3)
            for i in range(N_REQ)]
    with UMTRuntime(n_cores=4, umt=True, trace=True) as rt:
        with ServeEngine(b["cfg"], b["params"], slots=3,
                         cache_len=CACHE_LEN, rt=rt, jit_steps=b["steps"],
                         page_size=PAGE_SIZE, prefill_chunk=chunk) as eng:
            for r in reqs:
                eng.submit(r)
            eng.close()
            eng.join()
            stats = eng.stats()
        chunk_starts = [e for e in rt.tracer.events
                        if e[1] == "task_start"
                        and "serve.prefill.chunk" in str(e[4])]
    for r in reqs:
        assert np.array_equal(np.asarray(r.wait(), np.int32),
                              b["ref"][r.rid, :3])
    per_group = -(-PLEN // chunk)                   # ceil(8/3) = 3
    assert stats["prefill_chunks"] == per_group * stats["prefill_calls"]
    # the structural point: one task start per chunk
    assert len(chunk_starts) == stats["prefill_chunk_tasks"] \
        == stats["prefill_chunks"] > 0


@pytest.mark.slow
def test_engine_response_sink_and_weights_load_task(built):
    """Callable params (checkpointed-weights load) runs as a UMT task
    before the first prefill; the response sink sees every request."""
    from repro.serve import ServeEngine

    b = built
    seen = []
    loaded = []

    def load():
        loaded.append(True)
        return b["params"]

    with ServeEngine(b["cfg"], load, slots=2, cache_len=CACHE_LEN,
                     umt=True, n_cores=4, jit_steps=b["steps"],
                     response_sink=seen.append) as eng:
        reqs = [Request(i, b["prompts"][i], max_new_tokens=3)
                for i in range(4)]
        for r in reqs:
            eng.submit(r)
        eng.close()
        eng.join()

    assert loaded == [True]
    assert sorted(r.rid for r in seen) == [0, 1, 2, 3]
    for r in reqs:
        got = np.asarray(r.out_tokens, np.int32)
        assert np.array_equal(got, b["ref"][r.rid, :3])


@pytest.mark.slow
def test_spec_decode_ab_bit_identical_fewer_dispatches(built):
    """The tentpole A/B at the engine level: on a repetitive workload the
    n-gram drafter's accepted windows commit several tokens per verify
    dispatch, so the spec leg spends strictly fewer device dispatches per
    emitted token than tick-by-tick decode — with every stream (including
    an EOS early-exit) bit-identical across legs and to the one-shot
    reference, by construction."""
    import jax
    import jax.numpy as jnp
    from repro.steps import greedy_oneshot, make_serve_step

    b = built
    # templated workload: a 2-token motif tiled across the prompt makes
    # prompt-lookup hit from the very first decode tick
    prompts = np.array(b["prompts"], copy=True)
    prompts[:] = np.tile(prompts[:, :2], (1, PLEN // 2))
    serve_step = jax.jit(make_serve_step(b["cfg"]))
    ref = np.asarray(greedy_oneshot(b["steps"]["prefill"], serve_step,
                                    b["params"], jnp.asarray(prompts),
                                    None, GEN_MAX))
    eos = int(ref[0, GEN_MAX - 2])      # one stream exits inside a window

    def leg(spec):
        reqs = [Request(i, prompts[i], max_new_tokens=GEN_MAX,
                        eos_id=eos if i == 0 else None)
                for i in range(N_REQ)]
        stats, pager = _run_engine(b, reqs, spec=spec, spec_k=3)
        assert pager.live_refs == 0
        return [list(r.wait()) for r in reqs], stats

    toks_off, off = leg(None)
    toks_on, on = leg("ngram")
    assert toks_on == toks_off          # bit-identical, the hard gate
    for i, t in enumerate(toks_on):
        row = ref[i]
        assert t == list(row[:len(t)]) and (
            len(t) == GEN_MAX or row[len(t) - 1] == eos)
    assert off["spec"] == "off" and off["spec_drafted"] == 0
    assert on["spec"] == "ngram"
    assert on["spec_drafted"] > 0 and on["spec_accepted"] > 0
    assert 0.0 < on["spec_accept_rate"] <= 1.0
    # the win: same tokens, fewer dispatches
    assert on["dispatches_per_token"] < off["dispatches_per_token"]
    assert on["decode_dispatches"] < off["decode_dispatches"]


@pytest.mark.slow
@pytest.mark.parametrize("spec,trace", [(None, True), ("ngram", True),
                                        (None, False)])
def test_engine_stamps_and_spans(built, spec, trace):
    """Every admitted request is stamped in order (submit, first prefill
    dispatch, first token, admission, done), with tracing on or off; on
    a traced runtime the decode task records one ``tick`` span per tick,
    each admission an ``insert`` span with its rid, and ``stats()``
    reports each span's self time and the mean admission wait."""
    from repro.core import UMTRuntime
    from repro.serve import ServeEngine

    b = built
    reqs = [Request(i, b["prompts"][i], max_new_tokens=1 + i % GEN_MAX)
            for i in range(N_REQ)]
    with UMTRuntime(n_cores=4, umt=True, trace=trace) as rt:
        with ServeEngine(b["cfg"], b["params"], slots=3,
                         cache_len=CACHE_LEN, rt=rt, jit_steps=b["steps"],
                         page_size=PAGE_SIZE, spec=spec, spec_k=3) as eng:
            for r in reqs:
                eng.submit(r)
            eng.close()
            eng.join()
            stats = eng.stats()
        spans = rt.tracer.spans()
    for r in reqs:
        assert np.array_equal(np.asarray(r.wait(), np.int32),
                              b["ref"][r.rid, :r.max_new])
    admitted = [r for r in reqs if r.t_admit is not None]
    # a one-token request is done at prefill and never takes a slot
    assert {r.rid for r in admitted} == {r.rid for r in reqs
                                         if r.max_new > 1}
    for r in admitted:
        assert r.t_submit <= r.t_prefill <= r.t_first <= r.t_admit \
            <= r.t_done, r
    waits = [r.t_admit - r.t_submit for r in admitted]
    assert stats["admit_wait_mean_s"] == pytest.approx(np.mean(waits))
    if not trace:
        assert spans == [] and "span_self_s" not in stats
        return
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert len(by["tick"]) == stats["ticks"] > 0
    assert len(by["tick.dispatch"]) == stats["decode_dispatches"]
    assert len(by["prefill.first_token"]) == stats["prefill_calls"]
    # one tick.finish per completion in a tick, tagged with its rid
    assert sorted(s.rids[0] for s in by["tick.finish"]) == sorted(
        r.rid for r in admitted)
    assert {r.rid for r in admitted} <= {s.rids[0] for s in by["insert"]}
    ticks = {s.span_id for s in by["tick"]}
    for name in ("tick.policy", "tick.dispatch", "tick.finish"):
        assert all(s.parent in ticks for s in by[name]), name
    assert all(s.parent is None for s in by["insert"] + by["tick"])
    self_s = stats["span_self_s"]
    assert set(by) == set(self_s)
    assert {"tick", "tick.policy", "tick.dispatch", "tick.finish",
            "insert", "prefill.dispatch", "prefill.first_token"} <= set(by)
    assert all(v >= 0 for v in self_s.values())
