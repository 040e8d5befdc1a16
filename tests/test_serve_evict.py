"""Preemption-by-eviction correctness (policy layer + engine mechanism):

* evict -> restore greedy tokens are **bit-identical** to the
  never-evicted run — organically (on-demand paging into a tight pool)
  across all five frontends plus the SSM hybrid, and under forced fuzz
  evictions over the page-size x paged-kernel grid;
* the donation/pinning invariant holds under evict (no pinned leaf is a
  donated husk, and no freed page is read by a pending dispatch);
* on-demand allocation never deadlocks while the policy can always name
  one evictable victim (severe-pressure drain test with a watchdog);
* shared-prefix KV reuse serves bit-exact: a warm trie turns admissions
  into prefix hits (gather + tail chunks over shared pages) whose greedy
  tokens equal the cold one-shot run across the arch and page-size x
  paged-kernel grids, an evicted slot's pages are re-hit by its own
  restore, and the page_size=1 degenerate trie still saves the prefix.

Policy-decision unit tests (no jit) ride along, inner-loop fast."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get
from repro.models.lm import init_params
from repro.serve import (OnDemandPolicy, Request, SchedulerPolicy,
                         ServeEngine, make_jit_steps, make_policy)
from repro.serve.spec import NgramDrafter
from repro.steps import greedy_oneshot, make_serve_step

# plain GQA, SWA+MoE, MLA, vision frontend, audio frontend, SSM hybrid
ARCHS = ["qwen2.5-14b", "mixtral-8x7b", "minicpm3-4b", "internvl2-2b",
         "musicgen-large", "jamba-v0.1-52b"]
N_REQ, PLEN, GEN = 6, 8, 6


# --------------------------------------------------- policy units (fast)
def test_make_policy_resolution():
    assert make_policy(None).name == "reserve"
    assert make_policy("ondemand").on_demand
    p = OnDemandPolicy()
    assert make_policy(p) is p
    with pytest.raises(ValueError, match="unknown policy"):
        make_policy("nope")
    with pytest.raises(TypeError):
        make_policy(42)


def test_ondemand_victim_is_youngest():
    from repro.serve import SlotView

    views = [SlotView(slot=s, rid=s, admit_seq=seq, pages_held=2,
                      next_pos=9, emitted=2, budget=6)
             for s, seq in ((0, 5), (1, 9), (2, 7))]
    assert OnDemandPolicy().select_victim(None, views) == 1
    assert OnDemandPolicy().select_victim(None, []) is None
    assert SchedulerPolicy().select_victim(None, views) is None


def test_ondemand_policy_requires_paged_engine():
    """Every engine is paged: asking for no page size is refused."""
    cfg = get("qwen2.5-14b").tiny()
    with pytest.raises(ValueError, match="page_size"):
        ServeEngine(cfg, {}, slots=2, cache_len=8, page_size=None,
                    policy="ondemand")


# ----------------------------------------------- engine fuzz grid (slow)
def _build(arch, built):
    if arch not in built:
        cfg = get(arch).tiny()
        cache_len = PLEN + GEN + (
            cfg.n_patches if cfg.frontend == "vision_patches" else 0)
        ps = 2 if cache_len % 2 == 0 else 1   # small pages: growth fires
        params = init_params(cfg, jax.random.PRNGKey(0))
        shp = (N_REQ, PLEN) + ((cfg.n_codebooks,) if cfg.frontend ==
                               "audio_codebooks" else ())
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), shp, 0, cfg.vocab))
        patches = None
        if cfg.frontend == "vision_patches":
            patches = np.asarray(jax.random.normal(
                jax.random.PRNGKey(2), (N_REQ, cfg.n_patches, cfg.d_model),
                jnp.float32) * 0.02)
        steps = make_jit_steps(cfg, cache_len=cache_len, page_size=ps)
        serve_step = jax.jit(make_serve_step(cfg))
        ref = np.asarray(greedy_oneshot(
            steps["prefill"], serve_step, params, jnp.asarray(prompts),
            None if patches is None else jnp.asarray(patches), GEN))
        built[arch] = dict(cfg=cfg, params=params, cache_len=cache_len,
                           ps=ps, prompts=prompts, patches=patches,
                           steps=steps, ref=ref)
    return built[arch]


@pytest.fixture(scope="module")
def built():
    return {}


def _run(b, policy, *, num_pages=None, jit_steps=None, page_size="use",
        gens=None, eos=None, stop=None, slots=3, watchdog_s=None,
        spec=None, spec_k=4):
    """Drive one engine over the standard request set; assert every
    stream equals its one-shot row prefix and the pool drains clean.
    Returns the stats dict.  ``watchdog_s`` waits per request with a
    timeout instead of joining blind — a deadlock fails loudly instead
    of hanging the suite."""
    steps = b["steps"] if jit_steps is None else jit_steps
    ps = b["ps"] if page_size == "use" else page_size
    reqs = [Request(i, b["prompts"][i],
                    patches=None if b["patches"] is None
                    else b["patches"][i],
                    max_new_tokens=int(gens[i]) if gens is not None
                    else GEN,
                    eos_id=None if eos is None else eos[i],
                    stop=None if stop is None else stop[i])
            for i in range(N_REQ)]
    eng = ServeEngine(b["cfg"], b["params"], slots=slots,
                      cache_len=b["cache_len"], umt=True, n_cores=4,
                      jit_steps=steps, page_size=ps, num_pages=num_pages,
                      policy=policy, spec=spec, spec_k=spec_k)
    eng.kv.debug_validate = True      # donation/pinning invariant, live
    eng.start()
    for r in reqs:
        eng.submit(r)
    eng.close()
    if watchdog_s is not None:
        for r in reqs:
            r.wait(timeout=watchdog_s)
            assert r.done.is_set(), (
                f"request {r.rid} not done after {watchdog_s}s — "
                "on-demand admission deadlocked")
    eng.join()
    stats = eng.stats()
    eng.kv.assert_no_deleted_pins()   # (b) no pinned donated husk survives
    pager = eng.pager
    eng.shutdown()
    for r in reqs:
        got = np.asarray(r.wait(), np.int32)
        want = b["ref"][r.rid, :len(got)]
        assert np.array_equal(got, want), (
            f"request {r.rid}: evict/restore diverged from the "
            f"never-evicted run\n got {got}\nwant {want}")
        assert len(got) <= r.max_new
        if not r.stopped:
            assert len(got) == r.max_new
    assert pager.live_refs == 0, "page refs leaked across evictions"
    assert pager.used_pages == pager.cached_pages, (
        "pages leaked across evictions (allocated but neither held "
        "nor trie-cached)")
    return stats


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_evict_restore_bit_exact_under_pressure(arch, built):
    """(a) Organic preemption: on-demand paging into a pool that two
    requests can enter but cannot both finish in — growth collides,
    the policy evicts, the restore replays prefill over
    prompt+generated.  Greedy tokens must equal the never-evicted run
    on every frontend, including SSM state and vision-patch replay."""
    b = _build(arch, built)
    pager_probe = make_policy("ondemand")
    total = PLEN + (b["cfg"].n_patches
                    if b["cfg"].frontend == "vision_patches" else 0)
    p = -(-total // b["ps"])                      # prompt pages
    w = -(-(total + GEN - 1) // b["ps"])          # worst-case pages
    assert w > p, "geometry must force mid-decode growth"
    stats = _run(b, pager_probe, num_pages=p + w)  # capacity p+w-1
    assert stats["pages_grown"] > 0
    assert stats["evictions"] > 0, "tight pool never evicted"
    assert stats["restores"] == stats["evictions"]
    assert stats["policy"] == "ondemand"


class FuzzEvictPolicy(SchedulerPolicy):
    """Forced-preemption fuzz: every ``period`` ticks, evict a random
    live slot — exercises evict/restore on engines (worst-case
    reservation included) whose allocator would never preempt on its
    own."""

    def __init__(self, seed, period=3, max_evictions=4):
        self.rng = np.random.default_rng(seed)
        self.period = period
        self.left = max_evictions
        self.ticks = 0

    def maybe_evict(self, eng, views):
        self.ticks += 1
        if self.left <= 0 or not views or self.ticks % self.period:
            return None
        self.left -= 1
        return int(self.rng.choice([v.slot for v in views]))


class OnDemandFuzzEvict(FuzzEvictPolicy, OnDemandPolicy):
    """Fuzz evictions on top of on-demand admission/growth."""
    name = "ondemand"
    on_demand = True


def _grid_page_size(b, pages):
    """The page-size axis of the engine grids: one token per page, the
    small pages the fuzz grid runs, or one page per slot."""
    return {"token": 1, "small": b["ps"],
            "one_page": b["cache_len"]}[pages]


@pytest.mark.slow
@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("pages", ["token", "small", "one_page"])
def test_evict_grid_dense_paged_donation(pages, kernel, built):
    """(a) across the grid: forced fuzz evictions on page size {1, small,
    cache_len} x gather/fused-paged-kernel decode — including an eos
    request whose stop fired *before* an eviction could re-check it
    (restore must not re-emit or re-stop).  One page per slot runs under
    worst-case reservation, the others on demand.  Tokens bit-exact (the
    kernel rows therefore bit-identical to the gather rows), (b) the
    pinning invariant holds."""
    b = _build("qwen2.5-14b", built)
    ps = _grid_page_size(b, pages)
    steps = (b["steps"] if ps == b["ps"] and not kernel else
             make_jit_steps(b["cfg"], cache_len=b["cache_len"],
                            page_size=ps, paged_kernel=kernel))
    policy = (OnDemandFuzzEvict(seed=7) if pages != "one_page"
              else FuzzEvictPolicy(seed=7))
    eos = [None] * N_REQ
    eos[0] = int(b["ref"][0, 2])      # stops at its 3rd emitted token
    stats = _run(b, policy, jit_steps=steps, page_size=ps, eos=eos)
    assert stats["evictions"] > 0
    assert stats["page_size"] == ps
    assert stats["paged_kernel"] is kernel


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ondemand_never_deadlocks_under_severe_pressure(seed, built):
    """(c) Deadlock freedom: capacity exactly one request's worst case
    (the admission-validity minimum), fuzzed budgets and every slot
    fighting for pages — as long as the policy can name a victim, the
    engine must drain completely (watchdog-asserted, not hang) with
    every stream exact and every page returned."""
    b = _build("qwen2.5-14b", built)
    w = -(-(PLEN + GEN - 1) // b["ps"])
    gens = np.random.default_rng(seed).integers(1, GEN + 1, N_REQ)
    stats = _run(b, "ondemand", num_pages=w + 1, gens=gens,
                 watchdog_s=120)
    assert stats["requests"] == N_REQ
    assert stats["admission_blocks"] + stats["evictions"] > 0


class RestoreLandsMidAdmission(SchedulerPolicy):
    """Pushes a stand-in restore row to the head of the insert queue from
    inside the admission window — after the engine read the head, before
    it dequeued it — which is what a prefill worker finishing a restore
    does (``appendleft``) at any moment.  ``select_slot`` (called after
    the dequeue) records whether the engine dequeued the row it admitted
    and left the restore at the head."""

    def __init__(self):
        self.marker = self.admitting = self.head_kept = None

    def admission_tokens(self, eng, req):
        if self.marker is None:
            self.marker, self.admitting = ("restore",), req
            with eng._lock:
                eng._inserts.appendleft(self.marker)
        return super().admission_tokens(eng, req)

    def select_slot(self, eng, free):
        if self.head_kept is None:
            with eng._lock:
                head = eng._inserts[0] if eng._inserts else None
                self.head_kept = head is self.marker
                # take the stand-in (or, dequeued in its place, the
                # admitted row's stale twin) back out before the loop
                # tries to admit it
                if head is self.marker or (
                        head is not None and head[0] is self.admitting):
                    eng._inserts.popleft()
        return super().select_slot(eng, free)


@pytest.mark.slow
def test_admission_dequeues_the_row_it_admitted(built):
    """A restore pushed to the head of the insert queue while an
    admission is allocating pages must stay queued: the admission
    dequeues its own row, not whatever is at the head by then.
    Dequeuing the head instead loses the restore and leaves the admitted
    row queued twice — one request in two slots, its stream running
    ahead of its pages."""
    b = _build("qwen2.5-14b", built)
    policy = RestoreLandsMidAdmission()
    _run(b, policy)
    assert policy.head_kept is True


@pytest.mark.slow
def test_blocked_head_with_nothing_live_takes_queued_holds(built):
    """With no slot live, no page will ever be freed by a completion or
    an eviction: if the admission head cannot fit, the pages it lacks
    are prefix-cache holds of rows queued *behind* it, which only their
    own admission would release.  The engine must drop those holds (the
    rows fall back to cold-shaped admission, as a blocked head's own
    hold does) and admit the head — not block with nothing live, which
    the decode loop would spin on forever."""
    b = _build("qwen2.5-14b", built)
    cfg, ps, prefill = b["cfg"], b["ps"], b["steps"]["prefill"]
    w = -(-(PLEN + GEN - 1) // ps)          # one request's worst case
    eng = ServeEngine(cfg, b["params"], slots=2, cache_len=b["cache_len"],
                      umt=True, n_cores=2, jit_steps=b["steps"],
                      page_size=ps, num_pages=w + 1, policy="ondemand")
    try:
        # head: a restore of request 0 after 4 generated tokens (prompt +
        # generated[:-1] replayed); behind it: request 1, fresh
        gen0 = b["ref"][0, :5]
        replay = np.concatenate([b["prompts"][0], gen0[:-1]])
        rows = []
        for i, toks in ((0, replay), (1, b["prompts"][1])):
            req = Request(i, b["prompts"][i], max_new_tokens=GEN)
            cache, logits = prefill(b["params"], jnp.asarray(toks[None]),
                                    None)
            t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            req.total_len = len(toks)
            req.out_tokens = list(gen0) if i == 0 else [np.asarray(t0)[0, 0]]
            rows.append((req, cache, 0, t0))
        head, behind = rows[0][0], rows[1][0]
        head.restore_tokens = replay
        # the row behind the head holds cached pages of its prompt (a
        # prefix hit); the rest of the pool cannot fit the head
        n_hit = (PLEN - 1) // ps
        ids = eng.pager.alloc(n_hit)
        eng.prefix.insert(behind.tokens, ids, n_hit * ps)
        eng.pager.release(ids)
        behind.prefix_hold = eng.prefix.match_and_lock(behind.tokens,
                                                       PLEN - 1)
        assert behind.prefix_hold.pages == ids
        assert eng.pager.capacity - n_hit < \
            eng.pager.pages_for(head.total_len) <= eng.pager.capacity
        eng._inserts.extend(rows)
        eng._do_inserts()
        assert eng._active.any(), "head blocked with nothing live"
        assert eng._slot_req[int(np.flatnonzero(eng._active)[0])] is head
        assert behind.prefix_hold is None
        assert [e[0] for e in eng._inserts] == [behind]
    finally:
        eng.shutdown()


@pytest.mark.slow
def test_restore_retraces_bounded(built):
    """Eviction restores at many distinct depths must not pay one XLA
    retrace per distinct prompt+generated length: prefill-replay routes
    through the chunk step, whose shape set is bounded by the chunk
    geometry (last-chunk widths x extent buckets), not the restore
    count.  The one-shot prefill jit only ever sees new-prompt rounds —
    batch padded to powers of two, so at most 1 + log2(slots) shapes."""
    b = _build("qwen2.5-14b", built)
    slots = 3
    steps = make_jit_steps(b["cfg"], cache_len=b["cache_len"],
                           page_size=b["ps"])   # fresh: cache sizes ours
    assert steps["chunk"] is not None, "chunkable config must auto-chunk"
    policy = OnDemandFuzzEvict(seed=3, period=2, max_evictions=6)
    stats = _run(b, policy, jit_steps=steps, slots=slots)
    assert stats["restores"] >= 4, "fuzz produced too few restores"
    # restores happen at different ticks, so their prompt+generated
    # lengths differ — under one-shot-restore routing each distinct
    # depth would add a (1, depth) prefill trace and this bound breaks
    assert steps["prefill"]._cache_size() <= 1 + (slots - 1).bit_length()
    c = 1 << ((b["cache_len"] - 1).bit_length() // 2)
    n_buckets = -(-b["cache_len"] // c)
    assert steps["chunk"]._cache_size() <= (c + 1) * n_buckets, (
        "chunk-step traces exceeded the geometry bound — restore "
        "routing is leaking per-depth shapes")


# ------------------------------------- speculative decoding x churn (slow)
# the speculation gate: chunkable (extent-invariant) non-audio configs —
# exactly the prefill-replay restore population, so spec-mode evictions
# never meet the decode-replay path
SPEC_ARCHS = ["qwen2.5-14b", "minicpm3-4b", "internvl2-2b"]


def _ref_rows(b, prompts):
    """One-shot greedy rows for ``prompts`` (the harness's reference)."""
    serve_step = jax.jit(make_serve_step(b["cfg"]))
    patches = None if b["patches"] is None else jnp.asarray(b["patches"])
    return np.asarray(greedy_oneshot(
        b["steps"]["prefill"], serve_step, b["params"], jnp.asarray(prompts),
        patches, GEN))


def _bait_drafter(b, prompts):
    """Rewrite each prompt's last token so that the n-gram drafter must
    fire on the request's first decode tick, whatever the random weights
    emit: every vocabulary entry is tried in that position in one batched
    prefill, and the first whose greedy token already occurs in the
    prompt is kept — the drafter's 1-gram lookup then matches the emitted
    token against that occurrence.  Returns the prompts and their
    one-shot rows; asserts the opportunity holds on those rows."""
    n, vocab = len(prompts), b["cfg"].vocab
    cand = np.repeat(prompts, vocab, axis=0)
    cand[:, -1] = np.tile(np.arange(vocab), n)
    patches = (None if b["patches"] is None else
               jnp.asarray(np.repeat(b["patches"], vocab, axis=0)))
    _, logits = b["steps"]["prefill"](b["params"], jnp.asarray(cand),
                                      patches)
    first = np.asarray(jnp.argmax(logits, axis=-1))[:, 0].reshape(n, vocab)
    prompts = np.array(prompts, copy=True)
    for i in range(n):
        hits = [c for c in range(vocab)
                if first[i, c] == c or first[i, c] in prompts[i, :-1]]
        assert hits, f"no last token baits the drafter for prompt {i}"
        prompts[i, -1] = hits[0]
    ref = _ref_rows(b, prompts)
    drafter = NgramDrafter()
    for i in range(n):
        ctx = [int(t) for t in prompts[i]] + [int(ref[i, 0])]
        assert drafter.draft(ctx, 1), f"prompt {i}: bait missed"
    return prompts, ref


def _spec_data(b):
    """The standard request set with drafter-baited prompts
    (:func:`_bait_drafter`) plus matching one-shot rows, so speculation
    fires on every arch however the random weights decode."""
    if "spec_data" not in b:
        prompts, ref = _bait_drafter(b, b["prompts"])
        b["spec_data"] = dict(b, prompts=prompts, ref=ref)
    return b["spec_data"]


@pytest.mark.slow
@pytest.mark.parametrize("arch", SPEC_ARCHS)
def test_spec_decode_bit_exact_under_eviction_churn(arch, built):
    """Speculative decoding must not be able to change the emitted
    stream: spec=ngram under forced fuzz evictions (restore replays into
    a stream whose tail was committed multi-token) still emits every
    request's one-shot row bit-exactly (asserted by the harness), on
    GQA, MLA and the vision frontend."""
    b = _spec_data(_build(arch, built))
    stats = _run(b, OnDemandFuzzEvict(seed=5), spec="ngram")
    assert stats["spec"] == "ngram"
    assert stats["spec_drafted"] > 0, "drafter never fired"
    assert stats["evictions"] > 0
    assert stats["restores"] == stats["evictions"]
    # every tick is a verify dispatch; acceptance only lowers the ratio
    assert stats["dispatches_per_token"] <= 1.0


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["one_page", "small", "kernel"])
def test_spec_decode_grid_layout_donation(layout, built):
    """Spec decode across the layout grid — one page per slot, small
    pages, small pages with kernel-built decode (verify always reads
    through the gather+dense path — the fused kernel leg checks the
    engine tolerates kernel-built steps while speculating) — with an
    eos request in the mix and forced evictions.  Tokens bit-exact."""
    b = _spec_data(_build("qwen2.5-14b", built))
    ps = _grid_page_size(b, "small" if layout == "kernel" else layout)
    steps = make_jit_steps(b["cfg"], cache_len=b["cache_len"],
                           page_size=ps, paged_kernel=layout == "kernel")
    policy = (OnDemandFuzzEvict(seed=7) if layout != "one_page"
              else FuzzEvictPolicy(seed=7))
    eos = [None] * N_REQ
    eos[0] = int(b["ref"][0, 2])
    stats = _run(b, policy, jit_steps=steps, page_size=ps, eos=eos,
                 spec="ngram")
    assert stats["evictions"] > 0
    assert stats["spec_drafted"] > 0
    assert stats["page_size"] == ps


class OracleDrafter:
    """Drafts the one-shot row's true continuation (located by matching
    the slot's ctx against prompt+ref): every window is full length and
    fully accepted, deterministically, on any arch — the swap-in-a-
    better-drafter path the ``Drafter`` interface exists for."""

    name = "oracle"

    def __init__(self, prompts, ref):
        self.streams = [
            [int(t) for t in np.asarray(p).reshape(-1)] +
            [int(t) for t in r]
            for p, r in zip(prompts, ref)]

    def draft(self, ctx, k):
        n = len(ctx)
        for s in self.streams:
            if n <= len(s) and s[:n] == ctx:
                return s[n:n + k]
        return []


class OraclePolicy(OnDemandPolicy):
    """On-demand policy whose drafter is the oracle — speculation depth
    and drafter choice are policy decisions, so no engine change."""

    def __init__(self, b):
        self._drafter = OracleDrafter(b["prompts"], b["ref"])

    def spec_drafter(self, eng, mode):
        return self._drafter


@pytest.mark.slow
def test_spec_window_multipage_growth_under_pressure(built):
    """Satellite: spec_k >= page_size means one verify window crosses
    several page boundaries, so the on-demand fault pass must grow
    multiple pages for one slot in one tick (`pages_grown_multi`), and
    under a pool barely above one request's worst case that growth
    blocks and is unblocked by eviction.  The oracle drafter (plugged in
    through the policy hook) makes every window full length, so the
    multi-page fault is deterministic.  Streams stay bit-exact and the
    pool drains clean (harness)."""
    b = _build("qwen2.5-14b", built)          # ps == 2 < spec_k
    w = -(-(PLEN + GEN - 1) // b["ps"])       # worst-case pages/request
    stats = _run(b, OraclePolicy(b), num_pages=w + 2, spec="oracle",
                 spec_k=5, watchdog_s=120)
    assert stats["spec_drafted"] > 0
    assert stats["spec_accepted"] == stats["spec_drafted"], (
        "oracle drafts are the true continuation — rejecting one means "
        "the verify lanes disagree with tick-by-tick decode")
    assert stats["pages_grown"] > 0
    assert stats["pages_grown_multi"] > 0, (
        "no tick ever grew a slot by >1 page — the window fault pass "
        "is growing one page at a time")
    assert stats["evictions"] > 0, "tight pool never evicted"


@pytest.mark.slow
def test_spec_with_prefix_cache_bit_exact(built):
    """Spec decode on top of a warm radix trie: hit-path admissions
    land mid-page, verify windows must never write a shared or cached
    page (debug_validate asserts window write-privacy live), and the
    emitted streams still equal the cold one-shot rows."""
    b = _build("qwen2.5-14b", built)
    stats = _run_prefix(b, spec="ngram")
    assert stats["prefix_hits"] >= 1
    assert stats["prefix_tokens_saved"] > 0
    assert stats["spec_drafted"] > 0


# ------------------------------------------- eviction x stop (slow)
@pytest.mark.slow
@pytest.mark.parametrize("arch,kind", [("qwen2.5-14b", "eos"),
                                       ("qwen2.5-14b", "stop"),
                                       ("mixtral-8x7b", "eos"),
                                       ("mixtral-8x7b", "stop")])
def test_stop_fires_after_restore_on_both_replay_paths(arch, kind, built):
    """Satellite: a stop condition that completes only near the end of
    the stream meets forced evictions that land before it — the stop
    must fire *after* restore, identically on both restore shapes:
    prefill-replay (qwen2.5-14b, extent-invariant) and decode-replay
    (mixtral-8x7b, MoE capacity is extent-bound).  The harness asserts
    every truncated stream is the exact one-shot prefix; restores must
    not re-emit, re-check, or lose the recorded stop state."""
    b = _build(arch, built)
    eos = stop = None
    if kind == "eos":
        # fires at the second-to-last position (or wherever the value
        # first occurs — still a one-shot prefix either way)
        eos = [int(b["ref"][i, GEN - 2]) for i in range(N_REQ)]
    else:
        # two-token stop sequence completing late: its first token can
        # be committed before an eviction and completed after restore
        stop = [[[int(b["ref"][i, GEN - 3]), int(b["ref"][i, GEN - 2])]]
                for i in range(N_REQ)]
    policy = OnDemandFuzzEvict(seed=13, period=2, max_evictions=6)
    stats = _run(b, policy, eos=eos, stop=stop)
    assert stats["evictions"] > 0
    assert stats["restores"] == stats["evictions"]
    assert stats["stopped_early"] > 0, (
        "no stream stopped early — the stop tokens never matched")


# ------------------------------------------- prefix-cache rows (slow)
N_SHARED = 6          # shared system-prompt tokens (3 full pages, ps=2)


def _shared_prefix_data(b, bait=False):
    """The standard prompt set rewritten so every request shares its
    first ``N_SHARED`` tokens with request 0 (a common system prompt),
    plus the matching one-shot reference rows.  ``bait`` also rewrites
    each (unshared) last token so the n-gram drafter must fire
    (:func:`_bait_drafter`)."""
    key = "shared_bait" if bait else "shared"
    if key not in b:
        prompts = np.array(b["prompts"], copy=True)
        prompts[1:, :N_SHARED] = prompts[0, :N_SHARED]
        b[key] = (_bait_drafter(b, prompts) if bait
                  else (prompts, _ref_rows(b, prompts)))
    return b[key]


def _run_prefix(b, *, policy=None, num_pages=None, jit_steps=None,
                page_size="use", slots=3, prefix_cache="auto",
                spec=None, spec_k=4):
    """Drive one engine over the shared-prefix request set, request 0
    serialized to completion first so its pages warm the trie before
    the rest arrive.  Asserts every stream equals its one-shot row and
    the drained pool holds nothing but trie capital."""
    prompts, ref = _shared_prefix_data(b, bait=spec is not None)
    steps = b["steps"] if jit_steps is None else jit_steps
    ps = b["ps"] if page_size == "use" else page_size
    reqs = [Request(i, prompts[i],
                    patches=None if b["patches"] is None
                    else b["patches"][i],
                    max_new_tokens=GEN)
            for i in range(N_REQ)]
    eng = ServeEngine(b["cfg"], b["params"], slots=slots,
                      cache_len=b["cache_len"], umt=True, n_cores=4,
                      jit_steps=steps, page_size=ps, num_pages=num_pages,
                      policy=policy, prefix_cache=prefix_cache,
                      spec=spec, spec_k=spec_k)
    eng.kv.debug_validate = True
    eng.pager.debug_validate = True
    eng.start()
    eng.submit(reqs[0])
    reqs[0].wait(timeout=120)
    assert reqs[0].done.is_set(), "warm-up request did not finish"
    for r in reqs[1:]:
        eng.submit(r)
    eng.close()
    eng.join()
    stats = eng.stats()
    eng.kv.assert_no_deleted_pins()
    pager = eng.pager
    eng.shutdown()
    for r in reqs:
        got = np.asarray(r.wait(), np.int32)
        want = ref[r.rid, :len(got)]
        assert np.array_equal(got, want), (
            f"request {r.rid}: prefix-cache serving diverged from the "
            f"cold one-shot run\n got {got}\nwant {want}")
    assert pager.live_refs == 0, "prefix/page holds leaked"
    assert pager.used_pages == pager.cached_pages, (
        "pages leaked (allocated but neither held nor trie-cached)")
    return stats


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_prefix_hit_bit_exact_across_archs(arch, built):
    """Hit-path prefill (gather + tail chunks over shared pages) emits
    greedy tokens bit-identical to the cold one-shot run on every
    frontend; configs outside the chunk-exactness gate (and vision
    groups, whose patches make the prompt an incomplete key) bypass
    the prefix cache transparently and still serve exactly."""
    b = _build(arch, built)
    stats = _run_prefix(b)
    if b["patches"] is not None:
        assert stats["prefix_hits"] == 0     # vision groups skip the trie
    elif stats["prefix_cache"]:
        assert stats["prefix_hits"] >= 1, "shared prompts never hit"
        assert stats["prefix_tokens_saved"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("pages", ["token", "small"])
def test_prefix_hit_grid_donation_paged_kernel(pages, kernel, built):
    """Shared pages read identically through the gather+dense decode
    and the fused paged-attention kernel, at two page sizes that each
    leave a complete shared page — the garbage-masked insert and COW
    fork keep the donating steps' writes off shared pages on every
    leg."""
    b = _build("qwen2.5-14b", built)
    ps = _grid_page_size(b, pages)
    assert ps <= N_SHARED
    steps = make_jit_steps(b["cfg"], cache_len=b["cache_len"],
                           page_size=ps, paged_kernel=kernel)
    stats = _run_prefix(b, jit_steps=steps, page_size=ps)
    assert stats["prefix_hits"] >= 1
    assert stats["prefix_tokens_saved"] > 0
    assert stats["page_size"] == ps
    assert stats["paged_kernel"] is kernel


@pytest.mark.slow
def test_prefix_restore_rehits_trie(built):
    """An evicted slot's pages become trie capital: with *unique*
    prompts the only possible hit is a restore re-matching the pages
    its own eviction donated, so forced fuzz evictions must re-hit on
    every restore instead of replaying prefill cold."""
    b = _build("qwen2.5-14b", built)
    stats = _run(b, OnDemandFuzzEvict(seed=11))
    assert stats["restores"] > 0
    assert stats["prefix_hits"] >= stats["restores"], (
        "restore replayed prefill cold instead of re-hitting the trie")
    assert stats["prefix_tokens_saved"] > 0


@pytest.mark.slow
def test_prefix_page_size_one_degenerate(built):
    """page_size=1 (every token its own page): the trie degenerates to
    one node per token and a divergence page is always whole, so COW
    never fires — hits still save the full shared prefix, bit-exact."""
    b = _build("qwen2.5-14b", built)
    steps = make_jit_steps(b["cfg"], cache_len=b["cache_len"],
                           page_size=1)
    stats = _run_prefix(b, jit_steps=steps, page_size=1)
    assert stats["page_size"] == 1
    assert stats["prefix_hits"] >= 1
    assert stats["prefix_tokens_saved"] >= N_SHARED
    assert stats["cow_forks"] == 0


@pytest.mark.slow
def test_prefix_off_leg_serves_cold(built):
    """``prefix_cache="off"`` is the A/B leg: same engine, no trie, no
    shares — the shared-prompt set still serves bit-exact (asserted in
    the harness) and the drained pool caches nothing."""
    b = _build("qwen2.5-14b", built)
    stats = _run_prefix(b, prefix_cache="off")
    assert stats["prefix_cache"] is False
    assert stats["prefix_hits"] == 0
    assert stats["prefix_tokens_saved"] == 0


@pytest.mark.slow
def test_reserve_policy_never_faults_or_evicts(built):
    """The default policy is the pre-split engine bit-for-bit: worst-case
    reservation leaves nothing to grow and nobody to evict."""
    b = _build("qwen2.5-14b", built)
    stats = _run(b, None)
    assert stats["policy"] == "reserve"
    assert stats["pages_grown"] == 0
    assert stats["evictions"] == 0 and stats["restores"] == 0
