"""Serving load benchmark: Poisson arrivals against the continuous-batching
engine (paged KV + batched/chunked prefill; umt on/off) and the static
one-shot batch path.

Requests arrive with exponential inter-arrival gaps at a configurable
offered load (req/s) and identical prompts/generation budgets; every mode
serves the same arrival trace and must emit identical greedy tokens
(asserted).  Reported per (mode, load):

  * tokens/s        — total emitted tokens / wall (first arrival -> drain);
  * occupancy       — mean live-slot fraction per decode tick;
  * p50/p99 latency — per-request submit -> response (seconds);
  * pages_peak      — peak KV page-pool occupancy (paged modes);

Modes:

  * engine_umt   — ServeEngine on the UMT runtime: paged KV cache,
    arrivals coalesced into batched prefill rounds, request wait is a
    monitored block, a blocked core is backfilled (the paper's point, at
    the serving layer);
  * engine_base  — same engine, baseline runtime (blocked = idle core);
  * oneshot      — static batching: collect up to `slots` queued requests,
    prefill the batch, decode it to completion, repeat (pre-engine path).

Beyond the load sweep, targeted phases:

  * equal-memory slot capacity — at a one-full-cache-per-slot KV byte
    budget, the paged engine must sustain strictly more concurrent slots
    (short requests reserve only the pages they can touch, not
    cache_len);
  * chunked-prefill tick jitter — on a long+short prompt mix, chunked
    prefill (bounded cache-append calls, one continuation task per
    chunk) must cut the p99 decode-tick interval vs unchunked
    (sync_ticks=True so intervals measure real compute cadence);
  * scheduler-policy A/B (ISSUE 5) — on-demand paging + preemption-by-
    eviction vs worst-case reservation at equal KV memory: strictly more
    live slots (hard-asserted), then an eviction storm on a budget two
    requests cannot share (evictions > 0 hard-asserted, churn tail
    latency vs admission serialisation, tokens bit-identical throughout);
  * fused paged-attention kernel A/B (ISSUE 6) — decode via the
    in-kernel block-table walk vs the dense-gather materialisation:
    tokens/s and tick p50/p99 per leg on shared interleaved repeats,
    greedy tokens hard-asserted identical (off-TPU the kernel leg runs
    the interpret-mode emulation, so the row is integration evidence;
    the gather-elimination proof is benchmarks.kernels' HLO assertion);
  * shared-prefix KV reuse A/B (ISSUE 7) — the radix prefix cache on vs
    off at equal KV memory on a long shared system prompt + short unique
    tails: tokens bit-identical on both legs and prefix_tokens_saved > 0
    hard-asserted, hit tokens/s strictly above cold PASS-gated
    (``PREFIX_REUSE,...`` line);
  * speculative decoding A/B (ISSUE 8) — n-gram draft + batched verify
    vs tick-by-tick decode on a templated (tiled-motif) workload:
    tokens bit-identical across legs hard-asserted, spec_accepted > 0,
    device dispatches per emitted token strictly below 1.0 on the spec
    leg and strictly below the off leg's (``SPEC_DECODE,...`` line);
    wall-clock reported but not PASS-gated off-accelerator.
  * tensor-parallel serving A/B (ISSUE 9) — the sharded engine on a
    (1, ndev) device mesh vs the single-device engine at equal
    *per-device* KV memory (head-dim sharding holds 1/ndev of the pool
    per device, so ndev x the pages fit the same footprint): strictly
    more sustained live slots hard-asserted, tokens bit-identical,
    ``TP_SERVE,...`` PASS line; auto-skipped on one visible device
    (CI forces 4 host devices via XLA_FLAGS).

``--phases load,kernel,equal_mem,policy,prefix,spec,tp,jitter``
selects a subset (default all; ``--skip-phases`` = load only).

  python -m benchmarks.serve [--loads 32,256] [--requests 32] [--slots 4]
                             [--prompt-len 16] [--gen 16] [--cores 4]
                             [--page-size 0=auto] [--phases tp] [--smoke]
"""
from __future__ import annotations

import argparse
import threading
import time
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get
from repro.launch.mesh import make_mesh
from repro.launch.serve import _cache_len, _prompts
from repro.models.lm import init_params
from repro.serve import (Request, RequestQueue, ServeEngine, auto_page_size,
                         make_jit_steps)
from repro.serve.engine import percentile
from repro.steps import (chunkable, greedy_oneshot, make_prefill_step,
                         make_serve_step)


@dataclass
class ServeResult:
    name: str
    load: float
    requests: int
    slots: int
    wall_s: float
    tokens_s: float
    occupancy: float
    p50_s: float
    p99_s: float
    pages_peak: int | None = None
    pages_capacity: int | None = None
    max_live: int = 0
    prefill_calls: int = 0
    p50_tick_ms: float | None = None
    p99_tick_ms: float | None = None
    evictions: int = 0
    restores: int = 0
    pages_grown: int = 0
    admission_blocks: int = 0
    dispatches_per_token: float | None = None
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_rollbacks: int = 0

    def row(self) -> str:
        extra = ""
        if self.pages_peak is not None:
            extra = f",pages={self.pages_peak}/{self.pages_capacity}"
        if self.p99_tick_ms is not None:
            extra += f",p99_tick={self.p99_tick_ms:.1f}ms"
        if self.evictions or self.pages_grown:
            extra += (f",evict={self.evictions},grown={self.pages_grown}"
                      f",adm_blk={self.admission_blocks}")
        if self.name.startswith("serve_spec") and \
                self.dispatches_per_token is not None:
            extra += f",disp_tok={self.dispatches_per_token:.3f}"
        if self.spec_drafted:
            extra += (f",drafted={self.spec_drafted}"
                      f",accepted={self.spec_accepted}"
                      f",rollbacks={self.spec_rollbacks}")
        return (f"{self.name},load={self.load:g},req={self.requests},"
                f"tokens_s={self.tokens_s:.0f},occ={self.occupancy:.2f},"
                f"p50={self.p50_s * 1e3:.0f}ms,p99={self.p99_s * 1e3:.0f}ms"
                f",max_live={self.max_live},pf_calls={self.prefill_calls}"
                f"{extra}")


def _pct(xs, q):
    return percentile(sorted(xs), q)


def _mk_requests(prompts, patches, gens):
    return [Request(i, prompts[i],
                    patches=None if patches is None else patches[i],
                    max_new_tokens=int(gens[i]))
            for i in range(len(prompts))]


def _feed(submit, close, reqs, gaps):
    """Arrival process: submit each request after its exponential gap."""
    for r, g in zip(reqs, gaps):
        if g > 0:
            time.sleep(g)
        submit(r)
    close()


def run_engine(cfg, params, steps, prompts, gaps, *, gens, slots, cache_len,
               umt, cores, patches=None, name=None, page_size="auto",
               num_pages=None, prefill_chunk=None, sync_ticks=False,
               policy=None, spec=None, spec_k=4) -> tuple[ServeResult, list]:
    reqs = _mk_requests(prompts, patches, gens)
    with ServeEngine(cfg, params, slots=slots, cache_len=cache_len,
                     umt=umt, n_cores=cores, jit_steps=steps,
                     page_size=page_size, num_pages=num_pages,
                     prefill_chunk=prefill_chunk, sync_ticks=sync_ticks,
                     policy=policy, spec=spec, spec_k=spec_k) as eng:
        # timed region matches run_oneshot: first arrival -> drain (engine
        # construction/teardown excluded, like the oneshot jits are)
        t0 = time.monotonic()
        _feed(eng.submit, eng.close, reqs, gaps)
        eng.join()
        wall = time.monotonic() - t0
        st = eng.stats()
    toks = [np.asarray(r.out_tokens, np.int32) for r in reqs]
    lats = [r.latency for r in reqs]
    res = ServeResult(
        name=name or f"serve_engine_{'umt' if umt else 'base'}",
        load=0.0, requests=len(reqs), slots=slots, wall_s=wall,
        tokens_s=st["tokens_out"] / wall, occupancy=st["occupancy"],
        p50_s=_pct(lats, 0.50), p99_s=_pct(lats, 0.99),
        pages_peak=st.get("pages_used_peak"),
        pages_capacity=st.get("pages_capacity"),
        max_live=st["max_live_slots"], prefill_calls=st["prefill_calls"],
        p50_tick_ms=(st["p50_tick_s"] * 1e3
                     if st["p50_tick_s"] is not None else None),
        p99_tick_ms=(st["p99_tick_s"] * 1e3
                     if st["p99_tick_s"] is not None else None),
        evictions=st["evictions"], restores=st["restores"],
        pages_grown=st["pages_grown"],
        admission_blocks=st["admission_blocks"],
        dispatches_per_token=st.get("dispatches_per_token"),
        spec_drafted=st.get("spec_drafted", 0),
        spec_accepted=st.get("spec_accepted", 0),
        spec_rollbacks=st.get("spec_rollbacks", 0))
    return res, toks


def warm_engine_shapes(cfg, params, steps, prompts, patches, *, slots,
                       cache_len, cores, prefill_chunk=None):
    """Compile every jit shape a timed leg can hit: the engine buckets
    batched-prefill rounds to powers of two, so drive one pre-queued
    burst per bucket size (a burst queued before start coalesces into a
    single round of exactly that size) — without this, a timed leg pays
    a mid-run XLA compile the first time a new bucket shows up and every
    queued request behind it eats the stall."""
    sizes = sorted({min(1 << i, slots)
                    for i in range((max(slots - 1, 1)).bit_length() + 1)})
    for b in sizes:
        reqs = [Request(i, prompts[i],
                        patches=None if patches is None else patches[i],
                        max_new_tokens=2) for i in range(b)]
        eng = ServeEngine(cfg, params, slots=slots, cache_len=cache_len,
                          umt=True, n_cores=cores, jit_steps=steps,
                          page_size=steps["page_size"],
                          prefill_chunk=prefill_chunk)
        for r in reqs:
            eng.submit(r)
        with eng:
            eng.close()
            eng.join()


def run_oneshot(cfg, params, prefill, serve_step, prompts, gaps, *, gens,
                slots, patches=None) -> tuple[ServeResult, list]:
    """Static batching: up to ``slots`` queued requests per round; the
    whole batch decodes until its *longest* sequence finishes (finished
    requests hold their slot — the weakness continuous batching removes).
    """
    reqs = _mk_requests(prompts, patches, gens)
    q = RequestQueue()
    th = threading.Thread(target=_feed, args=(q.put, q.close, reqs, gaps))
    t0 = time.monotonic()
    th.start()
    ticks = occ = 0
    while True:
        r = q.get()
        if r is None:
            break
        batch = [r]
        while len(batch) < slots and len(q) > 0:
            batch.append(q.get())
        k = len(batch)
        bgen = max(b.max_new for b in batch)
        pad = [batch[0]] * (slots - k)           # pad rows: repeat req 0
        ptoks = np.stack([np.asarray(b.tokens) for b in batch + pad])
        pp = None if patches is None else \
            jnp.asarray(np.stack([b.patches for b in batch + pad]))
        outs = np.asarray(greedy_oneshot(prefill, serve_step, params,
                                         jnp.asarray(ptoks), pp, bgen))
        for t in range(bgen - 1):
            ticks += 1
            occ += sum(1 for b in batch if b.max_new - 1 > t) / slots
        t_end = time.monotonic()
        for j, b in enumerate(batch):
            b.out_tokens = list(outs[j, :b.max_new])
            b.t_first = b.t_done = t_end   # batch completes as one
            b.done.set()
    wall = time.monotonic() - t0
    th.join()
    toks = [np.asarray(r.out_tokens, np.int32) for r in reqs]
    lats = [r.latency for r in reqs]
    res = ServeResult(
        name="serve_oneshot", load=0.0, requests=len(reqs), slots=slots,
        wall_s=wall, tokens_s=sum(len(t) for t in toks) / wall,
        occupancy=occ / max(ticks, 1),
        p50_s=_pct(lats, 0.50), p99_s=_pct(lats, 0.99))
    return res, toks


def bench_equal_memory_slots(cfg, params, prefill, serve_step, *, slots,
                             cache_len, page_size, prompt_len, gen, cores,
                             n_req) -> ServeResult:
    """At a KV token budget of one full cache per slot (slots *
    cache_len), run the paged engine with a doubled slot pool and short
    requests: because each request reserves only
    ceil((prompt+gen-1)/page_size) pages instead of a full cache_len
    row, strictly more slots fit — one page per slot (``page_size ==
    cache_len``) cannot exceed ``slots`` concurrent requests at this
    memory no matter what arrives."""
    prompts, patches = _prompts(cfg, n_req, prompt_len, seed=5)
    prompts = np.asarray(prompts)
    patches = None if patches is None else np.asarray(patches)
    gens = np.full(n_req, gen)
    ref = np.asarray(greedy_oneshot(
        prefill, serve_step, params, jnp.asarray(prompts),
        None if patches is None else jnp.asarray(patches), gen))
    budget_pages = slots * cache_len // page_size   # a full cache per slot
    steps = make_jit_steps(cfg, cache_len=cache_len, page_size=page_size)
    res, toks = run_engine(
        cfg, params, steps, prompts, np.zeros(n_req), gens=gens,
        slots=2 * slots, cache_len=cache_len, umt=True, cores=cores,
        patches=patches, name="serve_paged_equal_mem",
        num_pages=budget_pages + 1)
    for i, t in enumerate(toks):
        assert np.array_equal(t, ref[i, :len(t)]), (
            f"equal-mem token mismatch @ request {i}")
    ok = res.max_live > slots
    print(res.row(), flush=True)
    print(f"  -> equal KV memory ({budget_pages} pages x {page_size} tok "
          f"= {slots} full-cache slots): paged sustained max_live="
          f"{res.max_live} slots — "
          f"{'PASS (strictly more)' if ok else 'FAIL'}",
          flush=True)
    return res


def bench_chunked_tick_jitter(cfg, params, *, prompt_len, long_factor, gen,
                              slots, cores, n_req, page_size, seed,
                              repeats=3) -> list[ServeResult]:
    """Sarathi scenario: a decode-resident batch keeps ticking while a
    coalesced burst of long prompts prefills (sync_ticks so intervals
    measure compute cadence).  Unchunked, each long round is one
    monopolising device computation that queued ticks wait out; chunked,
    every chunk completes (and hits a scheduling point) before the next
    dispatch, so ticks interleave at chunk granularity.

    This container's scheduling noise puts 40-100 ms spikes on even a
    bare single-threaded jit loop (reported below as the noise floor), so
    legs run interleaved `repeats` times and the PASS line compares the
    per-leg *median* of the run p99s."""
    import gc

    plen_long = prompt_len * long_factor
    cache_len = _cache_len(cfg, plen_long, gen)
    ps = page_size if cache_len % page_size == 0 else \
        auto_page_size(cache_len)
    res_gen = min(cache_len - prompt_len, 6 * gen)  # residents tick long
    n_burst = max(2 * slots, min(n_req, 8))
    short, _ = _prompts(cfg, slots, prompt_len, seed=3)
    longp, _ = _prompts(cfg, n_burst, plen_long, seed=4)
    short, longp = np.asarray(short), np.asarray(longp)

    prefill = jax.jit(make_prefill_step(cfg, cache_len=cache_len))
    serve_step = jax.jit(make_serve_step(cfg))
    ref_s = np.asarray(greedy_oneshot(prefill, serve_step, params,
                                      jnp.asarray(short), None, res_gen))
    ref_l = np.asarray(greedy_oneshot(prefill, serve_step, params,
                                      jnp.asarray(longp), None, gen))
    steps = make_jit_steps(cfg, cache_len=cache_len, page_size=ps,
                           chunk=True)
    chunk_size = max(4, plen_long // 8)
    for chunk in (None, chunk_size):
        for pr in (short, longp):      # warm both prompt shapes' buckets
            warm_engine_shapes(cfg, params, steps, pr, None, slots=slots,
                               cache_len=cache_len, cores=cores,
                               prefill_chunk=chunk)

    def leg(chunk):
        res = [Request(i, short[i], max_new_tokens=res_gen)
               for i in range(slots)]
        burst = [Request(100 + i, longp[i], max_new_tokens=gen)
                 for i in range(n_burst)]
        gc.disable()
        try:
            with ServeEngine(cfg, params, slots=slots, cache_len=cache_len,
                             umt=True, n_cores=cores, jit_steps=steps,
                             page_size=ps, prefill_chunk=chunk,
                             sync_ticks=True) as eng:
                t0 = time.monotonic()
                for r in res:
                    eng.submit(r)
                time.sleep(0.1)        # residents inserted and ticking
                for r in burst:
                    eng.submit(r)      # coalesced long-prefill rounds
                eng.close()
                eng.join()
                wall = time.monotonic() - t0
                st = eng.stats()
        finally:
            gc.enable()
        for i, r in enumerate(res):
            assert np.array_equal(np.asarray(r.out_tokens, np.int32),
                                  ref_s[i]), f"resident {i} mismatch"
        for i, r in enumerate(burst):
            assert np.array_equal(np.asarray(r.out_tokens, np.int32),
                                  ref_l[i]), f"burst {i} mismatch"
        return st, wall

    stats = {None: [], chunk_size: []}
    for _ in range(repeats):
        for chunk in (None, chunk_size):     # interleaved A/B
            stats[chunk].append(leg(chunk))
    out = []
    meds = {}
    for chunk, runs in stats.items():
        p99s = sorted(1e3 * s["p99_tick_s"] for s, _ in runs)
        p50s = sorted(1e3 * s["p50_tick_s"] for s, _ in runs)
        meds[chunk] = p99s[len(p99s) // 2]
        s, wall = runs[-1]
        r = ServeResult(
            name=f"serve_{'chunked' if chunk else 'unchunked'}_longmix",
            load=0.0, requests=slots + n_burst, slots=slots, wall_s=wall,
            tokens_s=s["tokens_out"] / wall, occupancy=s["occupancy"],
            p50_s=0.0, p99_s=0.0, pages_peak=s.get("pages_used_peak"),
            pages_capacity=s.get("pages_capacity"),
            max_live=s["max_live_slots"], prefill_calls=s["prefill_calls"],
            p99_tick_ms=meds[chunk])
        out.append(r)
        print(f"{r.name}: median p50_tick={p50s[len(p50s) // 2]:.1f}ms "
              f"median p99_tick={meds[chunk]:.1f}ms over {len(runs)} runs "
              f"(chunks/run={s['prefill_chunks']})", flush=True)
    ok = meds[chunk_size] < meds[None]
    verdict = "PASS (chunking cuts p99 tick jitter)" if ok else "FAIL"
    if not ok and plen_long < 256:
        verdict += (" — expected at this scale: a "
                    f"{plen_long}-token prefill is too short to "
                    "monopolise anything, chunking is pure overhead "
                    "(use --long-factor 32)")
    print(f"  -> long-prompt burst p99 tick (median of {repeats}): "
          f"unchunked {meds[None]:.1f}ms vs chunked "
          f"{meds[chunk_size]:.1f}ms — {verdict}", flush=True)
    return out


def bench_paged_kernel_ab(cfg, params, prompts, patches, gens, *, loads,
                          slots, cache_len, page_size, cores, seed,
                          repeats=3, steps_off=None) -> list[ServeResult]:
    """ISSUE 6 acceptance phase: the fused paged-attention decode kernel
    A/B'd against the dense-gather decode on the same arrival trace.

    Kernel-on and kernel-off legs share interleaved repeats at each
    load (``sync_ticks=True`` so tick quantiles measure compute
    cadence); per-leg tokens/s and tick p50/p99 medians are reported and
    greedy tokens are hard-asserted identical — the kernel is a memory-
    layout change, never a numbers change.  Off-TPU the kernel leg runs
    the interpret-mode emulation (same kernel, Python-level grid walk),
    so its wall-clock is a correctness harness, not the Mosaic timing:
    the gather-elimination evidence is benchmarks.kernels' HLO
    assertion; this phase pins the end-to-end engine integration."""
    legs = {}
    for kernel in (False, True):
        st = steps_off if not kernel else None
        if st is None:
            st = make_jit_steps(cfg, cache_len=cache_len,
                                page_size=page_size, paged_kernel=kernel)
            warm_engine_shapes(cfg, params, st, prompts, patches,
                               slots=slots, cache_len=cache_len,
                               cores=cores)
        legs[kernel] = st

    def _med(vals):
        xs = sorted(v for v in vals if v is not None)
        return xs[len(xs) // 2] if xs else float("nan")

    out = []
    for load in loads:
        gaps = np.random.default_rng(seed).exponential(
            1.0 / load, len(prompts))
        runs = {k: [] for k in legs}
        for _ in range(repeats):
            for kernel, st in legs.items():      # interleaved A/B
                res, toks = run_engine(
                    cfg, params, st, prompts, gaps, gens=gens,
                    slots=slots, cache_len=cache_len, umt=True,
                    cores=cores, patches=patches,
                    name=f"serve_paged_kernel_{'on' if kernel else 'off'}",
                    page_size=page_size, sync_ticks=True)
                res.load = load
                runs[kernel].append((res, toks))
        ref = runs[False][-1][1]
        for kernel, rs in runs.items():
            for _, toks in rs:
                for i, (a, b) in enumerate(zip(ref, toks)):
                    assert np.array_equal(a, b), (
                        f"paged-kernel A/B token mismatch: kernel="
                        f"{kernel} @ load {load}, request {i}")
        med = {}
        for kernel in (False, True):
            rs = [r for r, _ in runs[kernel]]
            r = rs[-1]
            r.tokens_s = _med(x.tokens_s for x in rs)
            r.wall_s = _med(x.wall_s for x in rs)
            r.occupancy = _med(x.occupancy for x in rs)
            r.p50_s = _med(x.p50_s for x in rs)
            r.p99_s = _med(x.p99_s for x in rs)
            r.p50_tick_ms = _med(x.p50_tick_ms for x in rs)
            r.p99_tick_ms = _med(x.p99_tick_ms for x in rs)
            med[kernel] = r
            out.append(r)
            print(r.row(), flush=True)
        ratio = med[True].tokens_s / med[False].tokens_s
        print(f"  -> paged-kernel A/B load={load:g} (median of "
              f"{repeats}): on/off tokens_s = {ratio:.2f}x "
              "(interpret emulation off-TPU), tick p50 "
              f"{med[True].p50_tick_ms:.1f} vs "
              f"{med[False].p50_tick_ms:.1f} ms, p99 "
              f"{med[True].p99_tick_ms:.1f} vs "
              f"{med[False].p99_tick_ms:.1f} ms — tokens bit-identical "
              "(PASS)", flush=True)
    return out


def bench_policy_phases(cfg, params, steps, prefill, serve_step, *, slots,
                        cache_len, page_size, prompt_len, gen, cores,
                        n_req, seed) -> list[ServeResult]:
    """ISSUE 5 acceptance phases: the scheduler-policy layer's first
    nontrivial policy — on-demand paging + preemption-by-eviction —
    measured against worst-case reservation at equal KV memory.

    Phase 1 (utilisation): a page budget that worst-case reservation can
    fill with exactly ``slots`` live requests; on-demand admission only
    reserves each prompt's pages, so it must sustain *strictly more*
    live slots on the same memory (hard-asserted — the admission path is
    capacity-driven, not timing-driven).

    Phase 2 (eviction storm): a budget two requests can enter but not
    finish in (``prompt_pages + worst_pages - 1``) — growth must
    collide, the policy must evict (``evictions > 0`` hard-asserted),
    and the tail latency of eviction churn is reported against the
    worst-case leg's admission serialisation on the same memory.
    Greedy tokens are asserted identical to the one-shot row in every
    leg — preemption may cost time, never correctness."""
    prompts, patches = _prompts(cfg, n_req, prompt_len, seed=11)
    prompts = np.asarray(prompts)
    patches = None if patches is None else np.asarray(patches)
    gens = np.full(n_req, gen)
    ref = np.asarray(greedy_oneshot(
        prefill, serve_step, params, jnp.asarray(prompts),
        None if patches is None else jnp.asarray(patches), gen))
    total = prompt_len + (cfg.n_patches
                          if cfg.frontend == "vision_patches" else 0)
    p = -(-total // page_size)                  # prompt pages
    w = -(-(total + gen - 1) // page_size)      # worst-case pages
    assert w > p, (
        f"page_size {page_size} never grows mid-decode for prompt "
        f"{total}+gen {gen} — pick a smaller --page-size for the "
        "policy phases")

    def leg(policy, name, budget, slots_leg):
        res, toks = run_engine(
            cfg, params, steps, prompts, np.zeros(n_req), gens=gens,
            slots=slots_leg, cache_len=cache_len, umt=True, cores=cores,
            patches=patches, name=name, page_size=page_size,
            num_pages=budget + 1, policy=policy)
        for i, t in enumerate(toks):
            assert np.array_equal(t, ref[i]), (
                f"{name}: token mismatch @ request {i} — eviction "
                "changed the stream")
        print(res.row(), flush=True)
        return res

    out = []
    # ---- phase 1: equal-KV-memory utilisation
    # budget invariant: worst-case reservation caps at `slots` live
    # (budget // w == slots, since p <= w - 1), while on-demand can
    # always admit a fresh prompt past `slots` fully-grown slots
    # (slots * w + p <= budget) — the strict max_live win is
    # admission-arithmetic, not a timing accident
    budget = slots * w + p
    legs = {pol: leg(pol, f"serve_{pol}_equal_mem", budget, 2 * slots)
            for pol in ("reserve", "ondemand")}
    out += legs.values()
    ok = legs["ondemand"].max_live > legs["reserve"].max_live
    print(f"  -> equal-KV-memory policy A/B ({budget} pages x "
          f"{page_size} tok): worst-case max_live="
          f"{legs['reserve'].max_live}, on-demand max_live="
          f"{legs['ondemand'].max_live} — "
          f"{'PASS (strictly more live slots)' if ok else 'FAIL'}",
          flush=True)
    assert ok, "on-demand paging did not lift live slots at equal memory"
    assert legs["reserve"].pages_grown == 0, (
        "worst-case reservation silently fell back to growth")

    # ---- phase 2: eviction storm
    budget = p + w - 1                 # two enter, both cannot finish
    legs = {pol: leg(pol, f"serve_{pol}_eviction_storm"
                     if pol == "ondemand" else f"serve_{pol}_storm_mem",
                     budget, slots)
            for pol in ("reserve", "ondemand")}
    out += legs.values()
    storm, rsv = legs["ondemand"], legs["reserve"]
    assert storm.evictions > 0, (
        "storm budget never forced an eviction — the mechanism did not "
        "fire")
    assert storm.restores == storm.evictions
    assert storm.pages_grown > 0
    print(f"  -> eviction storm ({budget} pages): evictions="
          f"{storm.evictions} restores={storm.restores} pages_grown="
          f"{storm.pages_grown} admission_blocks="
          f"{storm.admission_blocks}; p99 latency {storm.p99_s * 1e3:.0f}"
          f"ms (churn) vs {rsv.p99_s * 1e3:.0f}ms (worst-case "
          "serialisation) at equal memory — tokens bit-identical",
          flush=True)
    return out


def bench_prefix_reuse(cfg, params, *, slots, prompt_len, gen, cores,
                       n_req, page_size, seed, load=64.0,
                       repeats=3) -> list[ServeResult]:
    """ISSUE 7 acceptance phase: shared-prefix KV reuse (radix cache
    over refcounted pages) A/B'd against cold serving at equal KV
    memory.

    Every request carries the same *long* system prompt plus a short
    unique tail — the agent/chat pattern RadixAttention targets, sized
    so the shared prefill dominates per-request compute (long prefix,
    short tail, short decode: exactly the regime the optimisation is
    for).  One warm-up request runs to completion first (populating the
    radix trie on the hit leg), then the same Poisson trace runs with
    ``prefix_cache="on"`` and ``"off"`` on identical page budgets,
    interleaved ``repeats`` times with per-leg medians.

    Hard-asserted (not timing): greedy tokens on *both* legs are
    bit-identical to the cold one-shot reference, every post-warm
    request on the hit leg is a trie hit, and
    ``prefix_tokens_saved > 0``.  The PASS verdict additionally
    requires hit tokens/s strictly above cold."""
    sys_len = max(2 * page_size, page_size * ((8 * prompt_len)
                                              // page_size))
    cache_len = _cache_len(cfg, sys_len + prompt_len, gen)
    if cfg.frontend == "vision_patches" or not chunkable(cfg, cache_len):
        print("prefix-reuse phase: config cannot serve hits bit-exactly "
              "(no chunk-extent invariance) — skipped", flush=True)
        return []
    ps = page_size if cache_len % page_size == 0 else \
        auto_page_size(cache_len)
    base, _ = _prompts(cfg, 1, sys_len, seed=21)
    tails, _ = _prompts(cfg, n_req, prompt_len, seed=22)
    prompts = np.concatenate(
        [np.repeat(np.asarray(base), n_req, 0), np.asarray(tails)], axis=1)
    gens = np.full(n_req, gen)

    prefill = jax.jit(make_prefill_step(cfg, cache_len=cache_len))
    serve_step = jax.jit(make_serve_step(cfg))
    ref = np.asarray(greedy_oneshot(prefill, serve_step, params,
                                    jnp.asarray(prompts), None, gen))
    # both legs run chunked prefill (the long-prompt production setting,
    # PR 3): the cold leg pays ~sys/chunk cache-append dispatches per
    # request, the hit leg only the tail's — the dispatch+compute the
    # radix cache exists to skip
    chunk = max(4, sys_len // 8)
    steps = make_jit_steps(cfg, cache_len=cache_len, page_size=ps,
                           chunk=True)
    warm_engine_shapes(cfg, params, steps, prompts, None, slots=slots,
                       cache_len=cache_len, cores=cores,
                       prefill_chunk=chunk)
    # equal KV memory on both legs: a full cache per slot plus one
    # slot-equivalent of headroom so trie capital (the warm request's
    # pages idling at refcount 0) never fights live slots for pages
    pps = cache_len // ps
    num_pages = slots * pps + pps + 1
    gaps = np.random.default_rng(seed).exponential(1.0 / load, n_req - 1)

    def leg(prefix):
        warm = Request(0, prompts[0], max_new_tokens=int(gens[0]))
        rest = [Request(i, prompts[i], max_new_tokens=int(gens[i]))
                for i in range(1, n_req)]
        with ServeEngine(cfg, params, slots=slots, cache_len=cache_len,
                         umt=True, n_cores=cores, jit_steps=steps,
                         page_size=ps, num_pages=num_pages,
                         prefill_chunk=chunk,
                         prefix_cache=prefix) as eng:
            eng.submit(warm)
            warm.wait(timeout=300)      # trie warmed before the trace
            t0 = time.monotonic()
            _feed(eng.submit, eng.close, rest, gaps)
            eng.join()
            wall = time.monotonic() - t0
            st = eng.stats()
        for r in [warm] + rest:
            got = np.asarray(r.out_tokens, np.int32)
            assert np.array_equal(got, ref[r.rid, :len(got)]), (
                f"prefix-reuse A/B token mismatch: prefix={prefix} "
                f"request {r.rid} — reuse changed the stream")
        return st, gen * (n_req - 1) / wall, wall

    runs = {"on": [], "off": []}
    for _ in range(repeats):
        for prefix in ("on", "off"):          # interleaved A/B
            runs[prefix].append(leg(prefix))
    out = []
    med = {}
    for prefix, rs in runs.items():
        ts = sorted(t for _, t, _ in rs)
        med[prefix] = ts[len(ts) // 2]
        st, _, wall = rs[-1]
        if prefix == "on":
            assert st["prefix_hits"] >= n_req - 1, (
                "shared-prompt trace did not hit on every post-warm "
                f"request ({st['prefix_hits']}/{n_req - 1})")
            assert st["prefix_tokens_saved"] > 0, (
                "prefix hits saved no prefill tokens")
        else:
            assert st["prefix_hits"] == 0
        r = ServeResult(
            name=f"serve_prefix_{prefix}", load=load, requests=n_req,
            slots=slots, wall_s=wall, tokens_s=med[prefix],
            occupancy=st["occupancy"],
            p50_s=_pct([x or 0.0 for x in (st["p50_tick_s"],)], 0.5),
            p99_s=0.0, pages_peak=st.get("pages_used_peak"),
            pages_capacity=st.get("pages_capacity"),
            max_live=st["max_live_slots"],
            prefill_calls=st["prefill_calls"])
        out.append(r)
        print(r.row(), flush=True)
    st_on = runs["on"][-1][0]
    ratio = med["on"] / med["off"]
    ok = ratio > 1.0
    print(f"PREFIX_REUSE,sys={sys_len},tail={prompt_len},gen={gen},"
          f"req={n_req},hits={st_on['prefix_hits']},"
          f"tokens_saved={st_on['prefix_tokens_saved']},"
          f"cow_forks={st_on['cow_forks']},"
          f"page_shares={st_on['page_shares']},"
          f"on_tokens_s={med['on']:.1f},off_tokens_s={med['off']:.1f},"
          f"ratio={ratio:.2f}x,"
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    print(f"  -> prefix-reuse A/B (median of {repeats}, equal "
          f"{num_pages - 1}-page budget): hit leg "
          f"{'strictly above' if ok else 'NOT above'} cold at "
          f"{ratio:.2f}x tokens/s; tokens bit-identical on both legs, "
          f"{st_on['prefix_tokens_saved']} prefill tokens skipped",
          flush=True)
    return out


def bench_spec_decode(cfg, params, serve_step, *, slots, page_size,
                      prompt_len, gen, cores, n_req, seed, spec_k=4,
                      load=64.0, repeats=3) -> list[ServeResult]:
    """ISSUE 8 acceptance phase: speculative decoding (n-gram draft +
    batched verify) A/B'd against tick-by-tick decode on a workload
    where prompt-lookup drafting hits.

    Every prompt is a short motif tiled across its full length — the
    templated/repetitive regime n-gram drafting targets (the greedy
    continuation keeps landing inside a repeat the drafter has already
    seen).  The same arrival trace runs with ``spec="ngram"`` and
    ``spec=None`` (the off leg), interleaved ``repeats`` times.

    Hard-asserted (not timing): greedy tokens on both legs are
    bit-identical to each other and to the one-shot reference — the
    acceptance rule commits only verified argmaxes, so speculation can
    never change the stream — and on the spec leg ``spec_accepted > 0``
    with device **dispatches per emitted token strictly below 1.0** and
    strictly below the off leg's (one verify dispatch commits several
    tokens; the off leg's ratio is already < 1 under batching, which is
    why the cross-leg bound is the honest one).  Wall-clock tokens/s is
    reported but not PASS-gated: off-accelerator, verify lanes cost
    nearly nothing extra, but this container's timing noise drowns the
    win — the dispatch ledger is the deterministic measure (the PR 6
    interpret-mode precedent)."""
    from repro.steps import speculatable

    # a draft only pays off once the stream is long enough to repeat
    # (and k is clamped by the remaining budget), so the phase floors
    # the generation length — everything else follows the caller's size
    gen = max(gen, 8)
    cache_len = _cache_len(cfg, prompt_len, gen)
    if not speculatable(cfg, cache_len):
        print("spec-decode phase: config is not speculatable (needs "
              "chunk-exact prefill + token frontend) — skipped",
              flush=True)
        return []
    ps = page_size if cache_len % page_size == 0 else \
        auto_page_size(cache_len)
    steps = make_jit_steps(cfg, cache_len=cache_len, page_size=ps)
    prefill = steps["prefill"]
    raw, patches = _prompts(cfg, n_req, prompt_len, seed=31)
    prompts = np.array(raw, copy=True)
    m = 2 if prompt_len % 2 == 0 else 1
    prompts[:] = np.tile(prompts[:, :m], (1, prompt_len // m))
    patches = None if patches is None else np.asarray(patches)
    gens = np.full(n_req, gen)
    ref = np.asarray(greedy_oneshot(
        prefill, serve_step, params, jnp.asarray(prompts),
        None if patches is None else jnp.asarray(patches), gen))
    warm_engine_shapes(cfg, params, steps, prompts, patches, slots=slots,
                       cache_len=cache_len, cores=cores)
    gaps = np.random.default_rng(seed).exponential(1.0 / load, n_req)

    def leg(spec):
        res, toks = run_engine(
            cfg, params, steps, prompts, gaps, gens=gens, slots=slots,
            cache_len=cache_len, umt=True, cores=cores, patches=patches,
            name=f"serve_spec_{'on' if spec else 'off'}",
            page_size=ps, spec=spec, spec_k=spec_k)
        res.load = load
        for i, t in enumerate(toks):
            assert np.array_equal(t, ref[i]), (
                f"spec-decode A/B token mismatch: spec={spec} request "
                f"{i} — speculation changed the stream")
        return res, [list(t) for t in toks]

    leg("ngram")        # untimed: compile both verify shapes (S=1, S=k+1)
    runs = {"on": [], "off": []}
    for _ in range(repeats):
        for spec in ("ngram", None):          # interleaved A/B
            runs["on" if spec else "off"].append(leg(spec))
    assert runs["on"][-1][1] == runs["off"][-1][1], (
        "spec on/off legs disagree")          # and both == ref above

    def _med(vals):
        xs = sorted(v for v in vals if v is not None)
        return xs[len(xs) // 2] if xs else float("nan")

    out, med = [], {}
    for key, rs in runs.items():
        r = rs[-1][0]
        r.tokens_s = _med(x.tokens_s for x, _ in rs)
        r.wall_s = _med(x.wall_s for x, _ in rs)
        r.p50_s = _med(x.p50_s for x, _ in rs)
        r.p99_s = _med(x.p99_s for x, _ in rs)
        r.dispatches_per_token = _med(
            x.dispatches_per_token for x, _ in rs)
        med[key] = r
        out.append(r)
        print(r.row(), flush=True)
    on, off = med["on"], med["off"]
    rate = on.spec_accepted / max(on.spec_drafted, 1)
    ok = (on.spec_accepted > 0
          and on.dispatches_per_token < 1.0
          and on.dispatches_per_token < off.dispatches_per_token)
    print(f"SPEC_DECODE,plen={prompt_len},gen={gen},req={n_req},"
          f"k={spec_k},drafted={on.spec_drafted},"
          f"accepted={on.spec_accepted},acc_rate={rate:.2f},"
          f"rollbacks={on.spec_rollbacks},"
          f"disp_tok_on={on.dispatches_per_token:.3f},"
          f"disp_tok_off={off.dispatches_per_token:.3f},"
          f"on_tokens_s={on.tokens_s:.1f},off_tokens_s={off.tokens_s:.1f},"
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    print(f"  -> spec-decode A/B (median of {repeats}): tokens "
          "bit-identical on both legs; dispatches/token "
          f"{on.dispatches_per_token:.3f} (spec) vs "
          f"{off.dispatches_per_token:.3f} (off), acceptance "
          f"{rate:.0%} over {on.spec_drafted} drafts; tokens/s "
          f"{on.tokens_s:.1f} vs {off.tokens_s:.1f} (reported, not "
          "gated off-accelerator)", flush=True)
    assert on.spec_drafted > 0 and on.spec_accepted > 0, (
        "templated workload never produced an accepted draft")
    assert on.dispatches_per_token < 1.0, (
        "spec leg spent >= 1 dispatch per emitted token")
    assert on.dispatches_per_token < off.dispatches_per_token, (
        "speculation did not beat tick-by-tick on dispatches per token")
    return out


PHASES = ("load", "kernel", "equal_mem", "policy", "prefix",
          "spec", "tp", "jitter")


def bench_tp_serve(cfg, params, *, slots, cache_len, page_size,
                   prompt_len, gen, cores, n_req, seed) -> list[ServeResult]:
    """ISSUE 9 acceptance phase: tensor-parallel serving at equal
    *per-device* KV memory.

    The single-device engine gets a page budget that worst-case
    reservation fills with exactly ``slots`` live requests.  The sharded
    engine runs on a (1, ndev) mesh with ``ndev`` times the pages: its
    KV pool leaves shard the head dim ``1/ndev`` per device, so each
    device holds exactly the same pool bytes as the single-device leg
    (asserted on the biggest leaf, not claimed) — yet admission now has
    ``ndev`` times the page capacity, so it must sustain strictly more
    live slots (hard-asserted, capacity arithmetic not timing).  Greedy
    tokens are hard-asserted identical across both legs and the one-shot
    reference — sharding is a layout change, never a numbers change.
    Off-accelerator the devices are forced host threads, so tokens/s is
    reported but not PASS-gated; the capacity and footprint claims are
    device-count-real either way."""
    ndev = jax.device_count()
    if ndev == 1:
        print("tp-serve phase: one visible device — skipped (run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4 to "
              "exercise the sharded engine off-accelerator)", flush=True)
        return []
    mesh = make_mesh((1, ndev), ("data", "model"))
    prompts, patches = _prompts(cfg, n_req, prompt_len, seed=41)
    prompts = np.asarray(prompts)
    patches = None if patches is None else np.asarray(patches)
    gens = np.full(n_req, gen)
    prefill = jax.jit(make_prefill_step(cfg, cache_len=cache_len))
    serve_step = jax.jit(make_serve_step(cfg))
    ref = np.asarray(greedy_oneshot(
        prefill, serve_step, params, jnp.asarray(prompts),
        None if patches is None else jnp.asarray(patches), gen))
    total = prompt_len + (cfg.n_patches
                          if cfg.frontend == "vision_patches" else 0)
    w = -(-(total + gen - 1) // page_size)      # worst-case pages/request
    budget = slots * w                 # single-device cap: `slots` live

    def leg(name, steps, mesh_, num_pages):
        reqs = _mk_requests(prompts, patches, gens)
        with ServeEngine(cfg, params, slots=n_req, cache_len=cache_len,
                         mesh=mesh_, umt=True, n_cores=cores,
                         jit_steps=steps, page_size=page_size,
                         num_pages=num_pages) as eng:
            big = max(jax.tree.leaves(eng.kv.cache),
                      key=lambda x: x.nbytes)
            per_dev = big.addressable_shards[0].data.nbytes
            t0 = time.monotonic()
            _feed(eng.submit, eng.close, reqs, np.zeros(n_req))
            eng.join()
            wall = time.monotonic() - t0
            st = eng.stats()
        toks = [np.asarray(r.out_tokens, np.int32) for r in reqs]
        for i, t in enumerate(toks):
            assert np.array_equal(t, ref[i, :len(t)]), (
                f"tp-serve token mismatch: {name} request {i} — "
                "sharding changed the stream")
        lats = [r.latency for r in reqs]
        res = ServeResult(
            name=name, load=0.0, requests=n_req, slots=n_req, wall_s=wall,
            tokens_s=st["tokens_out"] / wall, occupancy=st["occupancy"],
            p50_s=_pct(lats, 0.50), p99_s=_pct(lats, 0.99),
            pages_peak=st.get("pages_used_peak"),
            pages_capacity=st.get("pages_capacity"),
            max_live=st["max_live_slots"],
            prefill_calls=st["prefill_calls"])
        print(res.row(), flush=True)
        return res, per_dev, toks

    steps1 = make_jit_steps(cfg, cache_len=cache_len, page_size=page_size)
    r1, dev1, toks1 = leg("serve_tp_single", steps1, None, budget + 1)
    steps_tp = make_jit_steps(cfg, mesh, cache_len=cache_len,
                              page_size=page_size, tp=True)
    rtp, devtp, tokstp = leg(f"serve_tp_shard{ndev}", steps_tp, mesh,
                             ndev * (budget + 1))
    assert [list(t) for t in toks1] == [list(t) for t in tokstp], (
        "tp-serve legs disagree")            # and both == ref above
    assert devtp == dev1, (
        f"per-device KV pool bytes differ: sharded {devtp} vs "
        f"single-device {dev1} — the head dim is not sharding 1/{ndev}")
    ok = rtp.max_live > r1.max_live
    print(f"TP_SERVE,mesh=1x{ndev},page={page_size},"
          f"pages={budget}->{ndev * budget},per_dev_pool_bytes={dev1},"
          f"max_live_single={r1.max_live},max_live_tp={rtp.max_live},"
          f"single_tokens_s={r1.tokens_s:.1f},"
          f"tp_tokens_s={rtp.tokens_s:.1f},bit_identical=True,"
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    print(f"  -> tp-serve equal per-device KV memory ({dev1} pool bytes "
          f"per device): single-device sustained max_live={r1.max_live} "
          f"slots, (1,{ndev})-sharded sustained max_live={rtp.max_live} "
          f"— {'PASS (strictly more live slots)' if ok else 'FAIL'}; "
          "tokens bit-identical (tokens/s reported, not gated on forced "
          "host devices)", flush=True)
    assert ok, (
        "tensor-parallel serving did not lift live slots at equal "
        "per-device KV memory")
    return [r1, rtp]


def main(argv=None) -> list[ServeResult]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--loads", default="32,256",
                    help="offered loads in req/s (comma-separated)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16,
                    help="max new tokens; per-request budgets are drawn "
                         "uniformly from [max(1, gen//4), gen]")
    ap.add_argument("--fixed-gen", action="store_true",
                    help="all requests generate exactly --gen tokens")
    ap.add_argument("--cores", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size (0 = largest divisor of cache_len "
                         "<= 8)")
    ap.add_argument("--long-factor", type=int, default=32,
                    help="jitter phase: long prompts are this multiple "
                         "of --prompt-len (long enough that one "
                         "unchunked prefill visibly monopolises)")
    ap.add_argument("--skip-phases", action="store_true",
                    help="load sweep only (skip equal-mem and jitter "
                         "phases); shorthand for --phases load")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of phases to run: "
                         f"{','.join(PHASES)} (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny everything: CI smoke config that still "
                         "executes every phase")
    args = ap.parse_args(argv)
    if args.smoke:
        args.loads, args.requests, args.slots = "64", 8, 2
        # 3 cores: the baseline (umt=False) leg needs intake + decode +
        # prefill workers
        args.prompt_len, args.gen, args.cores = 8, 4, 3
        args.long_factor = 8
        # small pages so the policy phases' mid-decode growth fires at
        # these tiny prompt/gen sizes (auto would cover gen in slack)
        args.page_size = args.page_size or 2
    loads = [float(x) for x in args.loads.split(",")]
    if args.phases is not None:
        phases = set(args.phases.split(","))
        unknown = phases - set(PHASES)
        if unknown:
            ap.error(f"unknown phases {sorted(unknown)}; "
                     f"choose from {','.join(PHASES)}")
    elif args.skip_phases:
        phases = {"load"}
    else:
        phases = set(PHASES)

    cfg = get(args.arch).tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache_len = _cache_len(cfg, args.prompt_len, args.gen)
    page_size = args.page_size or auto_page_size(cache_len)
    steps = make_jit_steps(cfg, cache_len=cache_len, page_size=page_size)
    prefill = steps["prefill"]
    serve_step = jax.jit(make_serve_step(cfg))
    # frontend-correct shapes (audio codebook dim, vision patches)
    prompts, patches = _prompts(cfg, args.requests, args.prompt_len)
    prompts = np.asarray(prompts)
    patches = None if patches is None else np.asarray(patches)
    rng = np.random.default_rng(args.seed)
    gens = (np.full(args.requests, args.gen) if args.fixed_gen else
            rng.integers(max(1, args.gen // 4), args.gen + 1,
                         args.requests))

    # warm every shape (oneshot batch prefill + serve step, and — via
    # throwaway engine legs — the engine's bucketed batched prefills,
    # insert + masked decode and the small eager ops) so no timed leg
    # pays XLA compile — only when a timed phase will run (capacity-
    # asserted phases like tp warm themselves or don't care)
    if phases & {"load", "kernel", "equal_mem", "policy"}:
        wp = None if patches is None else jnp.asarray(patches[:args.slots])
        cache, logits = prefill(params, jnp.asarray(prompts[:args.slots]),
                                wp)
        serve_step(params, cache, jnp.argmax(logits, -1).astype(jnp.int32))
        warm_engine_shapes(cfg, params, steps, prompts, patches,
                           slots=args.slots, cache_len=cache_len,
                           cores=args.cores)

    results: list[ServeResult] = []
    burst_ratio = None
    for load in loads if "load" in phases else []:
        gaps = np.random.default_rng(args.seed).exponential(
            1.0 / load, args.requests)
        runs = {}
        legs = [("serve_engine_umt", dict(umt=True, steps=steps,
                                          page_size=page_size)),
                ("serve_engine_base", dict(umt=False, steps=steps,
                                           page_size=page_size))]
        for name, kw in legs:
            res, toks = run_engine(
                cfg, params, kw["steps"], prompts, gaps, gens=gens,
                slots=args.slots, cache_len=cache_len, umt=kw["umt"],
                cores=args.cores, patches=patches, name=name,
                page_size=kw["page_size"])
            res.load = load
            runs[res.name] = (res, toks)
            results.append(res)
            print(res.row(), flush=True)
        res, toks = run_oneshot(cfg, params, prefill, serve_step, prompts,
                                gaps, gens=gens, slots=args.slots,
                                patches=patches)
        res.load = load
        runs[res.name] = (res, toks)
        results.append(res)
        print(res.row(), flush=True)

        # every mode serves the same trace -> identical greedy tokens
        ref = runs["serve_engine_umt"][1]
        for name, (_, toks) in runs.items():
            for i, (a, b) in enumerate(zip(ref, toks)):
                assert np.array_equal(a, b), (
                    f"token mismatch: serve_engine_umt vs {name} "
                    f"@ load {load}, request {i}")
        eng, base = runs["serve_engine_umt"][0], runs["serve_oneshot"][0]
        ub = runs["serve_engine_base"][0]
        burst_ratio = eng.tokens_s / base.tokens_s
        print(f"  -> load={load:g}: engine/oneshot tokens_s = "
              f"{burst_ratio:.2f}x, "
              f"p99 {eng.p99_s * 1e3:.0f}ms vs {base.p99_s * 1e3:.0f}ms; "
              f"umt/base = {eng.tokens_s / ub.tokens_s:.2f}x",
              flush=True)
    if burst_ratio is not None:
        ok = burst_ratio >= 1 / 1.2
        print(f"  -> burst check (load={loads[-1]:g}): batched prefill at "
              f"{burst_ratio:.2f}x of one-shot tokens/s — "
              f"{'PASS (within 1.2x)' if ok else 'FAIL (worse than 1.2x)'}",
              flush=True)

    if "kernel" in phases:
        # phase: fused paged-attention kernel A/B — in-kernel block-table
        # walk vs dense-gather decode, tokens hard-asserted identical
        results.extend(bench_paged_kernel_ab(
            cfg, params, prompts, patches, gens, loads=loads,
            slots=args.slots, cache_len=cache_len, page_size=page_size,
            cores=args.cores, seed=args.seed,
            repeats=1 if args.smoke else 3, steps_off=steps))

    if "equal_mem" in phases:
        # phase: strictly more concurrent slots at equal KV memory
        results.append(bench_equal_memory_slots(
            cfg, params, prefill, serve_step, slots=args.slots,
            cache_len=cache_len, page_size=page_size,
            prompt_len=max(2, args.prompt_len // 2),
            gen=max(2, args.gen // 4), cores=args.cores,
            n_req=args.requests))

    if "policy" in phases:
        # phase: policy A/B — on-demand paging + preemption-by-eviction
        # vs worst-case reservation (utilisation + eviction storm)
        results.extend(bench_policy_phases(
            cfg, params, steps, prefill, serve_step, slots=args.slots,
            cache_len=cache_len, page_size=page_size,
            prompt_len=args.prompt_len, gen=args.gen, cores=args.cores,
            n_req=args.requests, seed=args.seed))

    if "prefix" in phases:
        # phase: shared-prefix KV reuse A/B (ISSUE 7) — radix cache on
        # vs off at equal KV memory, warm trie, hit tokens/s vs cold
        results.extend(bench_prefix_reuse(
            cfg, params, slots=args.slots, prompt_len=args.prompt_len,
            gen=args.gen, cores=args.cores, n_req=args.requests,
            page_size=page_size, seed=args.seed))

    if "spec" in phases:
        # phase: speculative decoding A/B (ISSUE 8) — n-gram draft +
        # batched verify vs tick-by-tick, dispatch ledger hard-asserted
        results.extend(bench_spec_decode(
            cfg, params, serve_step, slots=args.slots,
            page_size=page_size, prompt_len=args.prompt_len,
            gen=args.gen, cores=args.cores, n_req=args.requests,
            seed=args.seed, repeats=1 if args.smoke else 3))

    if "tp" in phases:
        # phase: tensor-parallel serving (ISSUE 9) — equal per-device KV
        # memory, strictly more live slots, tokens bit-identical
        results.extend(bench_tp_serve(
            cfg, params, slots=args.slots, cache_len=cache_len,
            page_size=page_size, prompt_len=args.prompt_len,
            gen=args.gen, cores=args.cores, n_req=args.requests,
            seed=args.seed))

    if "jitter" in phases:
        # phase: chunked prefill bounds decode-tick jitter (chunk-exact,
        # token-only frontends: the mix builder has no patch plumbing)
        if cfg.frontend != "vision_patches" and chunkable(
                cfg, _cache_len(cfg, args.prompt_len * args.long_factor,
                                args.gen)):
            results.extend(bench_chunked_tick_jitter(
                cfg, params, prompt_len=args.prompt_len,
                long_factor=args.long_factor, gen=args.gen,
                slots=args.slots, cores=args.cores,
                n_req=args.requests, page_size=page_size,
                seed=args.seed))
    return results


if __name__ == "__main__":
    main()
