"""Continuous-batching serve engine on the UMT runtime — the *mechanism*
half of an explicit mechanism/policy split.

Every scheduling decision — admit or defer, how arrival rounds batch and
chunk, which slot an admission lands in, and (under memory pressure)
which victim is evicted — lives in :mod:`repro.serve.policy`; this module
keeps only mechanism: the UMT task graph, jit dispatch, donation/pinning
discipline, slot bookkeeping, and the evict/restore machinery the policy
drives.  The engine calls the policy at each decision point and executes
whatever comes back; swapping policies never touches device code.

A fixed pool of ``slots`` serve slots shares one batched KV cache.  The
linear attention cache leaves are **paged** (vLLM-style): physical pages
of ``page_size`` token slots from a free-list
(:class:`repro.serve.pager.PagePool`), freed the moment a request
finishes — so KV memory is bounded by *live tokens*, not by
``slots * cache_len``.  Bounded cache leaves (SWA rings, SSM conv/state)
stay dense per-slot rows.  How much is reserved at admission is the
policy's call: worst case (admission blocks on exhaustion, the default)
or on-demand (``policy="ondemand"``) — the prefill extent only, with the
slot's block table **grown page by page as decode crosses page
boundaries**.  On-demand exhaustion mid-decode is a *block* surfaced to
the policy, which unblocks it by **preemption**: the victim's pages are
freed, its request re-enters admission carrying generated-so-far tokens,
and the restore recomputes — one prefill over prompt + generated where
prefill is extent-invariant (the ``chunkable`` condition), a prefill of
the original prompt plus a decode-replay of the recorded tokens where
it is not (MoE capacity, SSD chunking, SWA rings) — so greedy output
stays bit-identical to the never-evicted run (tested across the fuzz
grid).

The cache pytree has a **single owner** — :class:`repro.serve.kvstate.
KVState` — and every cache-updating jit (decode, verify, insert, chunk)
**donates** it (``donate_argnums`` on the cache argument): XLA aliases
every cache leaf in place, so a decode tick or an insert never
materialises a copy of the KV pool.  Rebinding the live version goes
through :meth:`KVState.commit`; the versioned pinning of everything
else a pending dispatch reads is exclusive with donation: a donated
version is consumed by the computation that produced its successor, so
it is never pinned (asserted, tested).

Prefill is **batched** and **chunked**:

  * arrivals are coalesced per scheduling round (``RequestQueue.
    get_batch``) and prefilled as one batched call per prompt shape
    (batch padded to a power of two so jit shapes stay few) — closing the
    burst-throughput gap to the one-shot path's batched prefill;
  * with ``prefill_chunk=C`` set, long prompts prefill as cache-append
    chunks of ``C`` tokens (Sarathi-style): each chunk runs as its own
    **continuation task** (re-enqueued per chunk, not a loop inside one
    task), so concurrent long prefill rounds interleave fairly on a
    saturated pool and decode ticks slot in between chunks.

Everything I/O- or compute-shaped runs as a UMT task on the runtime:

  * **intake**   — blocks on the request queue (monitored ``io.wait``);
  * **prefill**  — one task per coalesced round, fanned out by intake;
  * **decode**   — the driver task: admit pending prefills (blocking on
    free pages, never corrupting), run one masked decode tick over the
    pool, collect finished/stopped slots; blocks (monitored) when no
    slot is live;
  * **respond**  — one task per finished request (response write through
    the monitored shim when a sink is configured);
  * **weights**  — optional checkpointed-weights load, so a core idled by
    request wait can load weights instead (paper's whole point).

Correctness bar (tested): for any arrival order, slot schedule, page
assignment and chunk boundaries, each request's greedy tokens are
bit-identical to (a prefix of, under ``eos_id``/``stop``) the one-shot
serve path's.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

from ..core import UMTRuntime, io
from ..sharding import logical_sharding
from ..steps import (TP_SERVE_RULES, chunkable, init_cache,
                     init_paged_slot_cache, make_batched_insert_step,
                     make_decode_step, make_prefill_chunk_step,
                     make_prefill_step, make_prefix_gather_step,
                     make_serve_step, make_verify_step, speculatable)
from .kvstate import KVState, alias_safe, cache_tree_shardings
from .pager import GARBAGE_PAGE
from .policy import SchedulerPolicy, SlotView, make_policy
from .prefix import PrefixCache
from .request import Request, RequestQueue

try:  # jax is present everywhere we run; guard only for doc tooling
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
except ImportError:  # pragma: no cover
    jax = jnp = NamedSharding = P = None


def percentile(xs, q):
    """Nearest-rank percentile of a pre-sorted list (None when empty) —
    shared by ``ServeEngine.stats`` and ``benchmarks/serve.py``."""
    return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else None


def auto_page_size(cache_len: int, cap: int = 8) -> int:
    """Largest divisor of ``cache_len`` that is <= ``cap``: big enough to
    keep block tables small, small enough that a short request does not
    reserve much slack past its last token."""
    return max(d for d in range(1, min(cap, cache_len) + 1)
               if cache_len % d == 0)


def _tp_shardings(cfg, mesh, cache_len: int, page_size: int):
    """Output-sharding trees for the tensor-parallel serve jits: the
    paged slot pool (``pool``), a prefill row cache (``row``) and
    a fully-replicated leaf (``rep`` — tokens and logits, which the
    model-axis all-reduce already materialises on every device).

    Donation aliases a sharded cache leaf only when the step's *output*
    sharding equals the (committed) input sharding, so every step's
    cache output is pinned to the exact per-leaf shardings ``KVState``
    commits its pool with — GSPMD never gets to re-decide a layout per
    step and silently break the alias.  ``NamedSharding`` is
    shape-independent, so nominal slots/num_pages hints are enough here:
    only the head/conv dims (taken from ``cfg``) decide the strict
    per-leaf resolution."""
    dt = jnp.dtype(cfg.dtype)
    pool = jax.eval_shape(
        lambda: init_paged_slot_cache(cfg, 1, cache_len, dt, page_size, 2))
    rows = jax.eval_shape(lambda: init_cache(cfg, 1, cache_len, dt))
    return {"rep": NamedSharding(mesh, P()),
            "pool": cache_tree_shardings(pool, mesh),
            "row": cache_tree_shardings(rows, mesh)}


def param_shardings(cfg, mesh):
    """Per-leaf ``NamedSharding`` tree of the weights under
    tensor-parallel serving: heads / ff fan-out / vocab on the model
    axis, strict — a dim the axis does not divide replicates."""
    from ..models.lm import LeafMeta, param_meta

    return jax.tree_util.tree_map(
        lambda m: logical_sharding(m.shape, m.axes, mesh, TP_SERVE_RULES,
                                   strict=True),
        param_meta(cfg), is_leaf=lambda x: isinstance(x, LeafMeta))


def make_jit_steps(cfg, mesh=None, cache_len: int = 64, *,
                   page_size: int | str = "auto", chunk: bool | None = None,
                   paged_kernel: bool = False, tp: bool = False):
    """The engine's jitted steps, built once — pass as ``jit_steps`` to
    several ``ServeEngine`` instances (benchmark A/B legs) so XLA compiles
    each step a single time per process.  Returns a dict carrying the
    cache geometry it was built for (the engine cross-checks it).
    ``page_size="auto"`` (default) is :func:`auto_page_size` of
    ``cache_len``, the engine's own rule.

    Every cache-updating step donates its cache argument: insert arg 0
    (the pool — never the shared prefill rows), decode and verify arg 1,
    chunk arg 1.  The producing computation then aliases every cache
    leaf in place (alias safety is asserted per leaf by the first engine
    built on the dict), so no step copies the pool.

    ``chunk=None`` (default) builds the chunked-prefill step whenever the
    config can chunk bit-exactly (``repro.steps.chunkable``) — jit is
    lazy, so an unused chunk step costs nothing, and the engine needs it
    to route eviction restores through bounded shapes instead of paying
    one XLA compile per distinct prompt+generated length.  ``False``
    omits it from the dict (an engine on a chunkable config still builds
    its own); ``True`` requires a chunkable config.

    ``paged_kernel=True`` builds the decode step on the fused
    paged-attention Pallas kernel (pages read in place, no dense
    ``page_gather`` per tick); default False keeps the dense-gather leg
    — the A/B baseline and bit-exactness oracle.

    ``tp=True`` builds every step tensor-parallel over ``mesh``'s model
    axis: cache head dims sharded per device (``repro.steps.
    TP_SERVE_RULES``), block tables / token rows / positions replicated,
    and each step's outputs pinned (``out_shardings``) to the same
    per-leaf shardings ``KVState`` commits its pool with — so donation
    keeps aliasing every sharded leaf in place, now per shard.  Greedy
    tokens stay bit-identical to the single-device engine (tested)."""
    if page_size == "auto":
        page_size = auto_page_size(cache_len)
    if tp and mesh is None:
        raise ValueError("tp=True needs a (data, model) mesh")
    if chunk is None:
        chunk = chunkable(cfg, cache_len)
    sh = _tp_shardings(cfg, mesh, cache_len, page_size) if tp else None
    rep = sh["rep"] if sh else None
    pool_sh = sh["pool"] if sh else None
    row_sh = sh["row"] if sh else None

    def _jit(fn, out, **kw):
        if sh is not None:
            kw["out_shardings"] = out
        return jax.jit(fn, **kw)

    ins = _jit(make_batched_insert_step(
        cfg, mesh, cache_len=cache_len, page_size=page_size, tp=tp),
        pool_sh, donate_argnums=(0,))
    dec = _jit(make_decode_step(
        cfg, mesh, cache_len=cache_len, page_size=page_size,
        paged_kernel=paged_kernel, tp=tp),
        (rep, pool_sh), donate_argnums=(1,))
    return {
        "cache_len": cache_len,
        "page_size": page_size,
        "paged_kernel": paged_kernel,
        "tp": tp,
        "prefill": _jit(make_prefill_step(cfg, mesh, cache_len=cache_len,
                                          tp=tp), (row_sh, rep)),
        "insert": ins,
        "decode": dec,
        # decode-replay restore (see ServeEngine._replay_generated) —
        # jit is lazy, so this compiles only if an eviction on a
        # non-extent-invariant config actually restores through it
        "replay": _jit(make_serve_step(cfg, mesh, tp=tp), (rep, row_sh)),
        "chunk": (_jit(make_prefill_chunk_step(cfg, mesh, cache_len,
                                               tp=tp),
                       (row_sh, rep),
                       donate_argnums=(1,),
                       static_argnames=("attn_extent", "want_logits"))
                  if chunk else None),
        # prefix-cache hit path (pure read of the pool — never donated):
        # gathers a matched prefix's shared pages into a fresh B=1 row
        # cache that seeds the tail chunk prefill
        "gather": (_jit(make_prefix_gather_step(
            cfg, mesh, cache_len=cache_len, page_size=page_size, tp=tp),
            row_sh) if chunkable(cfg, cache_len) else None),
        # speculative-decode verify (draft-and-verify multi-token decode,
        # see ServeEngine ``spec=``) — jit is lazy, so an unused verify
        # step costs nothing; None where the config cannot be bit-exact
        "verify": (_jit(make_verify_step(
            cfg, mesh, cache_len=cache_len, page_size=page_size, tp=tp),
            (rep, pool_sh), donate_argnums=(1,))
            if speculatable(cfg, cache_len) else None),
    }


class ServeEngine:
    """Continuous-batching engine over one model + one slot pool.

    Parameters
    ----------
    cfg : ModelConfig
    params : pytree or callable
        Model parameters, or a zero-arg callable (e.g. a checkpoint
        restore) run as a UMT task at start — weights loading overlaps
        request wait.
    slots : int
        Slot-pool size == decode batch.
    cache_len : int
        Logical per-slot cache length; every request needs
        ``prompt_len (+ n_patches) + max_new_tokens <= cache_len``.
    page_size : int | "auto"
        KV page size: a positive divisor of ``cache_len``, or "auto"
        (default), the largest divisor of ``cache_len`` <= 8
        (:func:`auto_page_size`).  ``page_size == cache_len`` gives each
        slot one page.
    num_pages : int, optional
        Physical pages including the reserved garbage page 0.  Default is
        one full cache per slot: ``slots * cache_len / page_size + 1``.
        A smaller pool admits fewer concurrent requests (admission blocks
        on the free list); a larger one admits more ``slots`` at the same
        per-request footprint.
    prefill_chunk : int, optional
        Chunked prefill: prompts longer than this prefill as cache-append
        chunks of this many tokens, one continuation task per chunk.
        Requires a chunk-exact config (``repro.steps.chunkable``) —
        raises ``ValueError`` otherwise.
    paged_kernel : bool, optional
        Decode attention reads KV pages in place through the fused
        paged-attention Pallas kernel (the per-tick dense ``page_gather``
        copy never materialises).  Default False keeps the gather+dense
        leg — the A/B baseline and bit-exactness oracle.  Must match
        ``jit_steps`` when both are given.
    tp : bool | None, optional
        Tensor-parallel serving: shard the decode/prefill/verify jits
        over ``mesh``'s model axis — every cache leaf with a head dim is
        split across the model devices (``repro.steps.TP_SERVE_RULES``,
        strict: a head count the axis cannot divide replicates), weights
        are sharded by their logical axes, and all host-side state
        (token rows, active masks, block tables, positions) is committed
        replicated.  Per-device KV bytes drop by the model-axis size, so
        the same per-device memory sustains more live slots.  Donation
        still aliases every sharded leaf in place (out_shardings pinned
        to the input layout) and greedy tokens stay bit-identical to the
        single-device engine.  Default ``None`` auto-enables exactly
        when ``mesh`` has a model axis larger than one; must match
        ``jit_steps`` when both are given.
    policy : SchedulerPolicy | str | None, optional
        The decision layer (see :mod:`repro.serve.policy`): None/"reserve"
        keeps worst-case page reservation at admission; "ondemand" turns
        on on-demand paging with preemption-by-eviction.  Any
        ``SchedulerPolicy`` instance plugs in custom decisions without
        touching the mechanism here.
    prefix_cache : bool | "on" | "off" | "auto" | None, optional
        Shared-prefix KV reuse (SGLang-style radix cache over the
        refcounted page pool): admission matches a prompt's longest
        cached prefix, points the slot's block table at the shared
        pages, and prefills only the uncached tail (copy-on-write fork
        at the divergence page).  Default ``None``/"auto" turns it on
        exactly when it can be bit-exact — a chunk-exact config
        (``repro.steps.chunkable``); non-qualifying configs (MoE,
        SSM/SSD, short SWA rings) bypass it transparently.  "on" raises
        on a non-qualifying engine; "off" disables it (the benchmark A/B
        leg).  Requests with ``patches`` never match (the trie keys on
        token ids alone).
    spec : str | None, optional
        Speculative decoding (draft-and-verify multi-token decode).
        ``None``/"off" keeps tick-by-tick decode (the A/B leg); "ngram"
        turns on n-gram/prompt-lookup drafting
        (:class:`repro.serve.spec.NgramDrafter`): each tick a drafter
        proposes up to ``spec_k`` continuation tokens per live slot and
        ONE verify dispatch scores the whole window; the longest
        agreeing draft prefix plus the model's correction is committed.
        Committed tokens are argmax outputs of the target model, so the
        emitted stream is **bit-identical to tick-by-tick decode by
        construction** — speculation only changes how many device
        dispatches it takes (< 1 per token when drafts hit).  Draft
        length and per-slot abandonment are policy decisions
        (``SchedulerPolicy.spec_draft_k``/``spec_drafter``).  Requires
        a chunk-exact config with a scalar token frontend
        (``repro.steps.speculatable``) — raises ``ValueError``
        otherwise.
    spec_k : int, optional
        Max draft window length (static verify pad width; default 4).
        Each spec engine compiles two verify shapes: S=1 (no slot
        drafted this tick) and S=spec_k+1.
    sync_ticks : bool
        Block on each decode tick before timestamping it — makes the
        tick-interval stats measure real compute cadence (benchmarks);
        leave False to keep the decode loop fully async.
    rt : UMTRuntime, optional
        Runtime to run on; when omitted the engine owns one
        (``umt``/``n_cores`` configure it).
    response_sink : callable, optional
        Called (monitored) with each finished request from its respond
        task — the "response write".
    """

    def __init__(self, cfg, params, *, slots: int = 4, cache_len: int = 64,
                 mesh=None, rt: UMTRuntime | None = None, umt: bool = True,
                 n_cores: int | None = None, response_sink=None,
                 idle_wait: float = 0.05, jit_steps=None,
                 page_size: int | str = "auto",
                 num_pages: int | None = None,
                 prefill_chunk: int | None = None,
                 max_prefill_batch: int | None = None,
                 sync_ticks: bool = False,
                 paged_kernel: bool | None = None,
                 tp: bool | None = None, policy=None,
                 prefix_cache: bool | str | None = None,
                 spec: str | None = None, spec_k: int = 4):
        self.cfg = cfg
        self.slots = slots
        self.cache_len = cache_len
        if page_size == "auto":
            page_size = (jit_steps["page_size"] if jit_steps is not None
                         else auto_page_size(cache_len))
        if not isinstance(page_size, (int, np.integer)) or page_size < 1 \
                or cache_len % page_size:
            raise ValueError(
                f"page_size={page_size!r}: pick 'auto' or a positive "
                f"divisor of cache_len={cache_len}")
        self.page_size = int(page_size)
        self.mesh = mesh
        self.response_sink = response_sink
        self.idle_wait = idle_wait
        self.sync_ticks = sync_ticks
        self.rt = rt if rt is not None else UMTRuntime(
            n_cores=n_cores, umt=umt, trace=False)
        self._own_rt = rt is None
        # spans of the decode and prefill tasks (a no-op unless the
        # runtime traces): ServeEngine.stats() reports their self time
        self._span = self.rt.tracer.span
        # the baseline runtime never backfills a blocked worker's core, so
        # intake (blocked on the queue) + the decode driver permanently
        # occupy two workers — prefill needs at least a third to make
        # progress (with UMT on, blocks are monitored and free their core)
        assert self.rt.umt or self.rt.n_cores >= 3, (
            "ServeEngine on a baseline (umt=False) runtime needs "
            "n_cores >= 3: intake and decode occupy a worker each")

        if jit_steps is not None:
            assert jit_steps["cache_len"] == cache_len, (
                "jit_steps were built for a different cache_len")
            assert jit_steps["page_size"] == page_size, (
                "jit_steps were built for a different page_size")
            steps_pk = jit_steps.get("paged_kernel", False)
            assert paged_kernel is None or paged_kernel == steps_pk, (
                "jit_steps were built for paged_kernel="
                f"{steps_pk}, engine asked for paged_kernel={paged_kernel}")
            paged_kernel = steps_pk
            steps_tp = jit_steps.get("tp", False)
            assert tp is None or tp == steps_tp, (
                f"jit_steps were built for tp={steps_tp}, "
                f"engine asked for tp={tp}")
            tp = steps_tp
        self.paged_kernel = bool(paged_kernel)
        # tensor-parallel serving auto-enables exactly when the mesh has
        # a model axis to shard over; a 1x1 host mesh (or no mesh) keeps
        # the single-device layout bit-for-bit
        if tp is None:
            tp = mesh is not None and mesh.shape.get("model", 1) > 1
        self.tp = bool(tp)
        if self.tp and mesh is None:
            raise ValueError("tp=True needs a (data, model) mesh")
        # XLA:CPU executes a sharded computation by fanning per-device
        # participant work onto one shared intra-op pool and
        # rendezvousing the participants inside each collective; two
        # sharded computations in flight can split the pool across
        # their rendezvous and starve each other (observed: concurrent
        # TP prefill rounds parked forever in AllReduce "waiting for
        # all participants").  Real accelerator backends order launches
        # per device stream, so only the (forced-host) CPU substrate
        # serializes: one sharded launch at a time, run to completion
        # under _dev_lock (see _dispatch).
        self._tp_serial = self.tp and jax.default_backend() == "cpu"
        self._dev_lock = threading.Lock()
        self.policy = make_policy(policy)
        # hot-path guard: only build per-tick SlotView snapshots for
        # policies that actually override the unforced-preemption hook
        self._policy_may_evict = (type(self.policy).maybe_evict
                                  is not SchedulerPolicy.maybe_evict)

        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            assert prefill_chunk >= 1
            if not chunkable(cfg, cache_len):
                raise ValueError(
                    f"{cfg.name}: chunked prefill needs linear-cache "
                    "attention blocks (no MoE, no SSM, no SWA ring "
                    "shorter than cache_len)")
        self.max_prefill_batch = max_prefill_batch or slots

        self.queue = RequestQueue()
        if jit_steps is None:
            jit_steps = make_jit_steps(cfg, mesh, cache_len,
                                       page_size=self.page_size,
                                       paged_kernel=self.paged_kernel,
                                       tp=self.tp)
        # fallback jits (an external jit_steps dict may omit entries)
        # must build with the same tp/out_shardings as make_jit_steps's
        tp_sh = (_tp_shardings(cfg, mesh, cache_len, self.page_size)
                 if self.tp else None)
        rep_sh = tp_sh["rep"] if tp_sh else None
        pool_sh = tp_sh["pool"] if tp_sh else None
        row_sh = tp_sh["row"] if tp_sh else None

        def _fb_jit(fn, out, **kw):
            if tp_sh is not None:
                kw["out_shardings"] = out
            return jax.jit(fn, **kw)

        self.prefill = jit_steps["prefill"]
        self.insert = jit_steps["insert"]
        self.decode = jit_steps["decode"]
        self.replay = jit_steps.get("replay") or _fb_jit(
            make_serve_step(cfg, mesh, tp=self.tp), (rep_sh, row_sh))
        self.chunk = jit_steps.get("chunk")
        # restore shape after an eviction: one prefill over
        # prompt+generated where prefill is extent-invariant (the
        # chunked-prefill condition — MoE capacity, SSD chunking and SWA
        # rings are extent-bound), decode-replay of the recorded tokens
        # otherwise (bit-exact by construction, a tick per token)
        self._restore_prefill = chunkable(cfg, cache_len)
        if self._restore_prefill and self.chunk is None:
            self.chunk = _fb_jit(
                make_prefill_chunk_step(cfg, mesh, cache_len, tp=self.tp),
                (row_sh, rep_sh), donate_argnums=(1,),
                static_argnames=("attn_extent", "want_logits"))
        # speculative decoding: spec mode resolves to a drafter (a policy
        # decision) + the verify jit; both shapes (S=1 and S=spec_k+1)
        # compile lazily on first use
        self.spec_mode = None if spec in (None, "off") else str(spec)
        self.spec_k = int(spec_k)
        self.verify = jit_steps.get("verify")
        self.drafter = None
        if self.spec_mode is not None:
            if self.spec_k < 1:
                raise ValueError(f"spec_k={spec_k}: need >= 1")
            if not speculatable(cfg, cache_len):
                raise ValueError(
                    f"{cfg.name}: speculative decoding needs a chunk-exact "
                    "config (no MoE, no SSM, no SWA ring shorter than "
                    "cache_len) and a scalar greedy-token frontend")
            if self.verify is None:
                self.verify = _fb_jit(
                    make_verify_step(cfg, mesh, cache_len=cache_len,
                                     page_size=self.page_size, tp=self.tp),
                    (rep_sh, pool_sh), donate_argnums=(1,))
            self.drafter = self.policy.spec_drafter(self, self.spec_mode)
        # chunk width for prefill-replay restores when the engine has no
        # steady-state prefill_chunk of its own: chunk-step shapes are
        # bounded by the chunk geometry (last-chunk widths <= c, extent
        # buckets <= cache_len/c) however many distinct restore depths
        # evictions produce, where one-shot prefill would retrace per
        # distinct prompt+generated length; ~sqrt(cache_len) balances
        # widths against buckets
        self.restore_chunk = prefill_chunk or (
            1 << ((cache_len - 1).bit_length() // 2))

        # shared-prefix KV reuse: qualifies exactly when the hit path can
        # be bit-exact — chunk-exact prefill (the tail chunks reproduce
        # the cold logits)
        can_prefix = self._restore_prefill
        if prefix_cache in (True, "on"):
            if not can_prefix:
                raise ValueError(
                    f"{cfg.name}: prefix_cache='on' needs a chunk-exact "
                    "config (repro.steps.chunkable) — "
                    "the hit path gathers shared pages into a row cache "
                    "and chunk-prefills the tail bit-exactly")
            self._use_prefix = True
        elif prefix_cache in (False, "off"):
            self._use_prefix = False
        elif prefix_cache in (None, "auto"):
            self._use_prefix = can_prefix
        else:
            raise ValueError(f"prefix_cache={prefix_cache!r}: pick "
                             "True/'on', False/'off' or None/'auto'")

        self._params = (None if callable(params)
                        else self._shard_params(params))
        self._params_fn = params if callable(params) else None
        self._params_ready = threading.Event()
        self._load_exc: BaseException | None = None
        if self._params_fn is None:
            self._params_ready.set()

        dt = jnp.dtype(cfg.dtype)
        # single owner of the cache pytree, the block tables and the page
        # free-list: every rebind goes through kv.commit, every buffer a
        # pending dispatch may read is pinned in kv
        self.kv = KVState(cfg, slots, cache_len, dt,
                          page_size=self.page_size,
                          num_pages=num_pages, mesh=mesh, tp=self.tp)
        self.pager = self.kv.pager
        self.pages_per_slot = self.kv.pages_per_slot
        # prefix trie + its gather jit; the pool lock orders the gather
        # dispatch (pure read of the live cache version) before any
        # donating dispatch of the same version (decode tick, insert) —
        # FIFO device execution then guarantees the gather reads the
        # buffers before the donating computation recycles them
        self.prefix = (PrefixCache(self.pager, self.page_size)
                       if self._use_prefix else None)
        self.gather = None
        if self.prefix is not None:
            self.gather = jit_steps.get("gather") or _fb_jit(
                make_prefix_gather_step(cfg, mesh, cache_len=cache_len,
                                        page_size=self.page_size,
                                        tp=self.tp),
                row_sh)
        self._pool_lock = threading.Lock()
        extra = ((cfg.n_codebooks,) if cfg.frontend == "audio_codebooks"
                 else ())
        # hot-path state is device-resident: the decode loop never syncs
        # to host — tokens are fetched once per *finished* request (plus
        # one small per-tick sync while a request with eos/stop rules is
        # live).  Device mirrors of host masks/tables are always
        # jnp.array (a copy): asarray may alias the numpy buffer, which
        # async dispatch could then read *after* a later host-side
        # mutation.
        self._tokens = self.kv.to_dev(np.zeros((slots, 1) + extra,
                                               np.int32))
        self._active = np.zeros((slots,), bool)
        self._active_dev = self.kv.to_dev(self._active)
        self._slot_req: list[Request | None] = [None] * slots
        # host-side per-slot scheduling state the policy decides over:
        # the cache position the next tick will write (drives on-demand
        # growth) and the admission sequence (drives victim ordering)
        self._slot_pos = np.zeros((slots,), np.int64)
        self._slot_seq = np.zeros((slots,), np.int64)
        self._admit_seq = 0
        self._blocked_head = None       # rid whose admission block we counted
        self._inserts: collections.deque = collections.deque()
        self._lock = threading.Lock()          # inserts/counters only
        self._pending_prefills = 0
        self._intake_done = False
        self._work = threading.Event()         # decode-driver doorbell
        self._started = False
        self._h_intake = self._h_decode = None

        # bounded stats state — a long-running engine must not retain
        # finished Request objects (prompts/patches/tokens) forever
        self._n_completed = 0
        self._tokens_out = 0
        self._lat_samples: collections.deque = collections.deque(
            maxlen=4096)
        self._ttft_samples: collections.deque = collections.deque(
            maxlen=4096)
        self._tick_intervals: collections.deque = collections.deque(
            maxlen=65536)
        self._admit_wait_sum = 0.0      # t_admit - t_submit, first admission
        self._n_admitted = 0
        self._last_tick_t: float | None = None
        self.stats_ticks = 0
        self.stats_occupancy_sum = 0.0
        self.stats_decode_tokens = 0
        self.stats_max_live_slots = 0
        self.stats_prefill_calls = 0
        self.stats_prefill_reqs = 0
        self.stats_prefill_chunks = 0
        self.stats_prefill_chunk_tasks = 0
        self.stats_stopped_early = 0
        # policy-mechanism counters: the bench phases assert these fired
        # (no silent fallback to worst-case reservation)
        self.stats_admission_blocks = 0
        self.stats_evictions = 0
        self.stats_restores = 0
        self.stats_pages_grown = 0
        # multi-token commits can cross several page boundaries per tick:
        # count the ticks where one slot grew more than one page at once
        self.stats_pages_grown_multi = 0
        # speculative decoding: dispatch/commit accounting.  The honest
        # measured axis on a dispatch-bound host is decode_dispatches /
        # decode_tokens — exactly 1.0 tick-by-tick, < 1.0 when drafts hit
        self.stats_decode_dispatches = 0
        self.stats_spec_drafted = 0
        self.stats_spec_accepted = 0
        self.stats_spec_rollbacks = 0
        # prefix-cache counters (satellite of the pager/trie stats):
        # tokens_saved = prompt positions the hit path never prefilled
        self.stats_prefix_hits = 0
        self.stats_prefix_tokens_saved = 0
        self.stats_cow_forks = 0

        # donation sanity, once per jit_steps dict (abstract eval only,
        # no compile): every cache leaf must come out of each donating
        # step with its input shape/dtype, or XLA could not alias the
        # donated buffer and would silently keep the full-pool copy
        if not jit_steps.get("_alias_ok"):
            self._assert_alias_safe()
            jit_steps["_alias_ok"] = True

    def _assert_alias_safe(self):
        from ..models.lm import cache_meta, meta_shape_structs, param_meta

        cfg = self.cfg
        ps = meta_shape_structs(param_meta(cfg),
                                jnp.dtype(cfg.param_dtype))
        kv, i32 = self.kv, jnp.int32
        scalar = jax.ShapeDtypeStruct((), i32)
        _, out_c = jax.eval_shape(self.decode, ps, kv.cache, self._tokens,
                                  self._active_dev, kv.table_dev)
        alias_safe(kv.cache, out_c, "decode")
        rows = meta_shape_structs(cache_meta(cfg, 1, self.cache_len),
                                  jnp.dtype(cfg.dtype))
        trow = jax.ShapeDtypeStruct((self.pages_per_slot,), i32)
        out_c = jax.eval_shape(self.insert, kv.cache, rows, scalar, scalar,
                               trow)
        alias_safe(kv.cache, out_c, "insert")
        if self.chunk is not None:
            tok = jax.ShapeDtypeStruct(
                (1, 1) + ((cfg.n_codebooks,)
                          if cfg.frontend == "audio_codebooks" else ()),
                i32)
            out_c, _ = jax.eval_shape(
                lambda p, rc, t, off: self.chunk(
                    p, rc, t, off, None, attn_extent=self.cache_len,
                    want_logits=False),
                ps, rows, tok, scalar)
            alias_safe(rows, out_c, "chunk")

    def _shard_params(self, params):
        """Commit the weights to :func:`param_shardings`; identity when
        not tensor-parallel (and a no-op for weights created with those
        shardings already)."""
        if not self.tp or params is None:
            return params
        return jax.device_put(params, param_shardings(self.cfg, self.mesh))

    def _dev_rows(self, rows):
        """Commit a fresh host-built row cache to its per-leaf TP
        shardings (the chunk jit donates it — aliasing needs the input
        already laid out); identity when not tensor-parallel."""
        if not self.tp:
            return rows
        return jax.device_put(rows, self.kv.cache_shardings(rows))

    def _dispatch(self, step, *args, **kw):
        """Run one jitted engine step.  Tensor-parallel on the CPU
        backend serializes — at most one sharded computation in flight,
        completed before the lock releases (collective-rendezvous
        starvation, see ``_tp_serial`` in ``__init__``); every other
        configuration is a plain async dispatch."""
        if not self._tp_serial:
            return step(*args, **kw)
        with self._dev_lock:
            out = step(*args, **kw)
            jax.block_until_ready(out)
            return out

    # ------------------------------------------------------------ lifecycle
    def start(self):
        assert not self._started
        self._started = True
        if self._params_fn is not None:
            self.rt.submit(self._load_params, name="serve.weights")
        self._h_intake = self.rt.submit(self._intake, name="serve.intake")
        self._h_decode = self.rt.submit(self._decode_loop,
                                        name="serve.decode")
        return self

    def submit(self, req: Request):
        self.queue.put(req)

    def close(self):
        """No more submissions; queued/in-flight requests still finish."""
        self.queue.close()

    def join(self):
        """Wait for intake + decode to drain (call after :meth:`close`)."""
        if self._h_intake is not None:
            self._h_intake.wait()
        if self._h_decode is not None:
            self._h_decode.wait()
        self.rt.wait_all()

    def shutdown(self):
        self.close()
        if self._started:
            self.join()
        if self._own_rt:
            self.rt.shutdown()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()

    # ------------------------------------------------------------ the tasks
    def _load_params(self):
        try:
            self._params = self._shard_params(self._params_fn())
        except BaseException as e:     # noqa: BLE001 — re-raised by prefill
            self._load_exc = e
            raise
        finally:
            self._params_ready.set()   # hang-proof: waiters always released
            self._work.set()

    def _intake(self):
        while True:
            # monitored block for the first arrival, then coalesce the
            # round's worth of already-queued prompts into one prefill
            # task (batched prefill; the round cap is a policy decision)
            batch = self.queue.get_batch(
                self.policy.prefill_batch_cap(self))
            if batch is None:
                break
            with self._lock:
                self._pending_prefills += len(batch)
            self.rt.submit(self._prefill_round, batch,
                           name=f"serve.prefill:{batch[0].rid}"
                                f"x{len(batch)}")
        with self._lock:
            self._intake_done = True
        self._work.set()

    def _validate(self, req: Request):
        """Admission-impossible geometry fails loudly at prefill time (not
        assert: under python -O an oversized request would decode past the
        cache and silently emit corrupt tokens).  Restore replays carry
        their generated prefix in the prompt, so the budget check uses
        the *remaining* token budget — the sum is invariant across
        evictions.  The single-request worst-case-fits-the-pool check is
        also what makes on-demand eviction deadlock-free: a lone live
        slot can always grow."""
        p = self.cfg.n_patches \
            if self.cfg.frontend == "vision_patches" else 0
        req.total_len = int(np.asarray(req.prefill_tokens).shape[0]) + p
        # decode ticks still owed after this round's prefill: the fresh
        # prefill emits one token for free, a restore replay emits none
        # (its argmax is already in out_tokens) — either way the sum
        # below is invariant across evictions
        ticks = req.max_new - max(len(req.out_tokens), 1)
        if req.total_len + ticks + 1 > self.cache_len:
            return ValueError(
                f"request {req.rid}: prompt {req.total_len} + "
                f"{ticks + 1} tokens to go exceeds cache_len "
                f"{self.cache_len}")
        need = self.pager.pages_for(req.total_len + ticks)
        if need > self.pager.capacity:
            return ValueError(
                f"request {req.rid}: needs {need} KV pages but the "
                f"pool only has {self.pager.capacity} — it can never "
                "be admitted")
        if req.needs_host_tokens and \
                self.cfg.frontend == "audio_codebooks":
            return ValueError(
                f"request {req.rid}: eos_id/stop are not supported for "
                "audio-codebook frontends")
        return None

    def _drop_prefix_hold(self, req: Request):
        """Release the pager holds a request's prefix match still
        carries — failure/finish paths where the admission that would
        have adopted them never happened (or already did: no-op)."""
        if req.prefix_hold is not None and self.prefix is not None:
            self.prefix.release(req.prefix_hold)
            req.prefix_hold = None

    def _drop_queued_holds(self) -> bool:
        """Drop the prefix holds of every row queued for insertion;
        True if any was held."""
        with self._lock:
            held = [e[0] for e in self._inserts
                    if e[0].prefix_hold is not None]
        for req in held:
            self._drop_prefix_hold(req)
        return bool(held)

    def _finish_failed(self, req: Request, exc: BaseException):
        self._drop_prefix_hold(req)
        if not req.done.is_set():
            req.error = exc
            req.t_done = time.monotonic()
            req.done.set()
        with self._lock:
            self._pending_prefills -= 1
        self._work.set()

    def _prefill_round(self, reqs):
        """One coalesced prefill round: validate, group by prompt shape,
        run one batched (optionally chunked) prefill per group, and queue
        the rows for insertion."""
        remaining = list(reqs)
        try:
            io.wait(self._params_ready)
            if self._load_exc is not None:
                raise RuntimeError("weights load failed") \
                    from self._load_exc
            groups: dict = {}
            for req in reqs:
                err = self._validate(req)
                if err is not None:
                    remaining.remove(req)
                    self._finish_failed(req, err)
                else:
                    key = (np.asarray(req.prefill_tokens).shape,
                           req.patches is not None)
                    groups.setdefault(key, []).append(req)
            exc0 = None
            for grp in groups.values():
                try:
                    # _prefill_group removes each request from
                    # ``remaining`` the moment it is accounted (insert
                    # queued / finished), so a mid-group failure fails
                    # exactly the unaccounted ones — never double-counts
                    self._prefill_group(grp, remaining)
                except BaseException as e:      # noqa: BLE001
                    for r in grp:
                        if r in remaining:
                            remaining.remove(r)
                            self._finish_failed(r, e)
                    if exc0 is None:
                        exc0 = e
            if exc0 is not None:
                raise exc0
        except BaseException as e:              # noqa: BLE001
            for r in remaining:
                self._finish_failed(r, e)
            remaining.clear()
            raise
        finally:
            self._work.set()

    def _prefill_group(self, grp, remaining):
        """Batched prefill of same-shape prompts; rows are queued for
        insertion and sliced into slots by the decode driver.  The batch
        is padded to the next power of two (repeating the last row) so
        the jit sees a handful of shapes, not one per burst size —
        per-row outputs are extent-invariant, so padding cannot perturb
        the real rows.

        Long prompts under ``prefill_chunk`` do not prefill here: the
        group is handed to a chunk *continuation chain* (one UMT task
        per chunk, see :meth:`_prefill_chunk_task`) and leaves
        ``remaining`` — the chain owns its accounting from then on.

        Prefix-cache hits leave the group first: each hit becomes its
        own tail-only chunk chain (B=1 — its prefill extent differs from
        the cold rows'), so a warm prompt never drags a cold batch
        through a full prefill, and vice versa."""
        if self.prefix is not None and grp[0].patches is None:
            grp = [r for r in grp
                   if not self._try_prefix_prefill(r, remaining)]
            if not grp:
                return
        bg = len(grp)
        toks = np.stack([np.asarray(r.prefill_tokens) for r in grp])
        patches = None
        if grp[0].patches is not None:
            patches = np.stack([np.asarray(r.patches) for r in grp])
        bpad = 1 << (bg - 1).bit_length()
        if bpad > bg:
            toks = np.concatenate(
                [toks, np.repeat(toks[-1:], bpad - bg, axis=0)])
            if patches is not None:
                patches = np.concatenate(
                    [patches, np.repeat(patches[-1:], bpad - bg, axis=0)])
        tj = self.kv.to_dev(toks)
        pj = None if patches is None else self.kv.to_dev(patches)

        if self.chunk is not None and grp[0].resume \
                and grp[0].restore_tokens is not None:
            # prefill-replay restore: prompt+generated length varies with
            # eviction depth, so a one-shot prefill here would pay one
            # XLA retrace per distinct length.  Route through the chunk
            # step instead — its shapes are bounded by the chunk width
            # (<= restore_chunk last-chunk widths + cache_len/chunk
            # extent buckets) no matter how many evictions restore.
            chunk = self.restore_chunk
        else:
            chunk = (self.policy.chunk_len(self, grp[0].total_len)
                     if self.chunk is not None else None)
        if chunk is not None:
            st = {"rows_cache": self._dev_rows(
                      init_cache(self.cfg, bpad, self.cache_len,
                                 jnp.dtype(self.cfg.dtype))),
                  "off": 0, "c0": 0, "first": True, "chunks": 0,
                  "chunk": int(chunk), "unaccounted": list(grp)}
            for r in grp:
                remaining.remove(r)
            try:
                self.rt.submit(self._prefill_chunk_task, grp, tj, pj, st,
                               name=f"serve.prefill.chunk:{grp[0].rid}@0")
            except BaseException as e:          # noqa: BLE001
                for r in st["unaccounted"]:     # chain never started
                    self._finish_failed(r, e)
                st["unaccounted"] = []
                raise
            return
        with self._span("prefill.dispatch", [r.rid for r in grp]):
            rows_cache, logits = self._dispatch(self.prefill, self._params,
                                                tj, pj)
        self._stamp_prefill(grp)
        self._account_prefilled(grp, remaining, rows_cache, logits)

    @staticmethod
    def _stamp_prefill(reqs):
        """``t_prefill``: the request's first prefill dispatch (one-shot,
        first chunk or prefix gather); a restore keeps the first."""
        now = time.monotonic()
        for r in reqs:
            if r.t_prefill is None:
                r.t_prefill = now

    def _try_prefix_prefill(self, req, remaining) -> bool:
        """Prefix-cache hit path.  Match the prompt's longest cached
        prefix (full pages + a partial run into the divergence page —
        the COW fork source), gather the matched pages' content into a
        fresh B=1 row cache, then chunk-prefill **only the uncached
        tail** from the divergence position — the gathered K/V are a
        pure copy of pages an identical-prefix prefill wrote, so the
        tail chunks (extent-invariant by the ``chunkable`` gate) emit
        logits bit-identical to a cold prefill.  The fork is
        copy-on-write realised through gather + fresh-page insert: the
        source page is never written, the admitted slot's own page
        receives the copy.  Returns True when the request took this
        path (a chunk chain owns its accounting from then on)."""
        toks = np.asarray(req.prefill_tokens)
        plen = len(toks)
        if plen < 2 or self.chunk is None:
            return False
        # cap at plen - 1: the last position is always recomputed (the
        # tail chunk must produce last-token logits)
        m = self.prefix.match_and_lock(toks, plen - 1)
        if m.tokens == 0:
            return False
        try:
            kv = self.kv
            trow = np.full((self.pages_per_slot,), GARBAGE_PAGE, np.int32)
            trow[:len(m.pages)] = m.pages
            if m.fork_src is not None:
                trow[len(m.pages)] = m.fork_src
            # gather inputs stay locals until the sync below (the
            # documented backend buffer-lifetime rule); the pool lock
            # orders this dispatch before any donating decode/insert of
            # the same cache version — FIFO device execution then runs
            # the gather before the donating step recycles the buffers
            trow_dev, pos_dev = kv.to_dev(trow), kv.to_dev(
                np.int32(m.tokens))
            with self._span("prefill.dispatch", (req.rid,)), \
                    self._pool_lock:
                src = kv.cache
                rows_cache = self._dispatch(self.gather, src, trow_dev,
                                            pos_dev)
            self._stamp_prefill((req,))
            with self._span("prefill.sync", (req.rid,)):
                jax.block_until_ready(rows_cache["pos"])
            del src, trow_dev, pos_dev
            # fork content copied: drop its hold (the matched full
            # pages' holds ride to admission on the request)
            self.prefix.release_fork(m)
            req.prefix_hold = m
            with self._lock:
                self.stats_prefix_hits += 1
                self.stats_prefix_tokens_saved += m.tokens
                if m.fork_len:
                    self.stats_cow_forks += 1
        except BaseException:                   # noqa: BLE001
            self.prefix.release(m)
            raise
        remaining.remove(req)
        st = {"rows_cache": rows_cache, "off": m.tokens, "c0": m.tokens,
              "first": False, "chunks": 0,
              "chunk": int(self.restore_chunk), "unaccounted": [req]}
        tj = self.kv.to_dev(toks[None])
        try:
            self.rt.submit(self._prefill_chunk_task, [req], tj, None, st,
                           name=f"serve.prefill.hit:{req.rid}@{m.tokens}")
        except BaseException as e:              # noqa: BLE001
            for r in st["unaccounted"]:         # chain never started
                self._finish_failed(r, e)
            st["unaccounted"] = []
            raise
        return True

    def _prefill_chunk_task(self, grp, tj, pj, st):
        """One bounded cache-append chunk of a chunked prefill round,
        **re-enqueued as a continuation task per chunk**: the ready
        queue interleaves two concurrent long rounds' chunk tasks fairly
        on a saturated pool, where a loop inside one task would hold its
        worker for the whole prefill (ROADMAP "chunked prefill across
        rounds").  The chain owns the group's failure accounting —
        anything unaccounted fails loudly if a chunk raises."""
        try:
            plen = tj.shape[1]
            npatch = 0 if pj is None else pj.shape[1]
            c = st["chunk"]
            c0, off, first = st["c0"], st["off"], st["first"]
            c1 = min(c0 + c, plen)
            covered = off + (c1 - c0) + (npatch if first else 0)
            # static extent bucket (multiple of the chunk size, so jits
            # are reused across rounds): total attention FLOPs stay at
            # the one-shot level; non-final chunks skip the LM head
            ext = min(self.cache_len, -(-covered // c) * c)
            old_rows = st["rows_cache"]
            # dispatch temporaries bound as locals: the chunk slice and
            # offset must stay referenced until the sync below, or a
            # pending dispatch could read their recycled buffers (the
            # documented backend bug — same rule as kv.pin in
            # _do_inserts)
            chunk_toks, off_dev = tj[:, c0:c1], self.kv.to_dev(
                np.int32(off))
            rids = [r.rid for r in grp]
            with self._span("prefill.dispatch", rids):
                rows_cache, logits = self._dispatch(
                    self.chunk, self._params, old_rows, chunk_toks, off_dev,
                    pj if first else None, attn_extent=ext,
                    want_logits=c1 >= plen)
            self._stamp_prefill(grp)
            st.update(rows_cache=rows_cache, off=covered, c0=c1,
                      first=False, chunks=st["chunks"] + 1)
            # complete the chunk before the next task dispatches it:
            # back-to-back async chunks would occupy the device queue
            # exactly like one long prefill — the bounded gap (plus the
            # task boundary, a scheduling point like any other) is where
            # decode ticks interleave.  ``old_rows`` stays referenced
            # until this sync, so the chunk chain never drops a version
            # a pending dispatch still reads.
            with self._span("prefill.sync", rids):
                jax.block_until_ready(rows_cache["pos"])
            del old_rows, chunk_toks, off_dev
            with self._lock:
                self.stats_prefill_chunk_tasks += 1
            if c1 < plen:
                self.rt.submit(self._prefill_chunk_task, grp, tj, pj, st,
                               name=f"serve.prefill.chunk:"
                                    f"{grp[0].rid}@{c1}")
                return
            with self._lock:            # rounds run on concurrent workers
                self.stats_prefill_chunks += st["chunks"]
            self._account_prefilled(grp, st["unaccounted"], rows_cache,
                                    logits)
        except BaseException as e:              # noqa: BLE001
            for r in list(st["unaccounted"]):
                self._finish_failed(r, e)
            st["unaccounted"] = []
            raise
        finally:
            self._work.set()

    def _account_prefilled(self, grp, remaining, rows_cache, logits):
        """Hand a prefilled group to the decode driver: stamp TTFT, emit
        the prefill token, finish done-at-prefill requests, queue the
        rest for insertion.  Removes each request from ``remaining`` the
        moment it is accounted, so a mid-group failure fails exactly the
        unaccounted ones."""
        t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # force the first token before stamping TTFT — dispatch is
        # async, so a monotonic() above the sync would under-report;
        # the prefill runs behind every decode tick already dispatched
        with self._span("prefill.first_token", [r.rid for r in grp]):
            t0.block_until_ready()
        t0_host = np.asarray(t0)        # already forced: free
        now = time.monotonic()
        with self._lock:                # rounds run on concurrent workers
            self.stats_prefill_calls += 1
            self.stats_prefill_reqs += len(grp)
        for i, r in enumerate(grp):
            remaining.remove(r)
            if r.resume:
                # restore (recompute-on-restore): every replayed token
                # was already emitted (and stop-checked) before the
                # eviction, so nothing is appended, TTFT keeps the
                # original first-token stamp, and the row re-enters at
                # the *head* of the admission queue so evicted requests
                # outrank new arrivals (no restore starvation)
                assert len(grp) == 1, "restore rounds are singleton"
                if r.restore_tokens is None:
                    # decode-replay: the prefill covered the original
                    # prompt only and re-derived the first token; feed
                    # the recorded stream back through the serve step
                    assert np.array_equal(t0_host[i, 0],
                                          r.out_tokens[0]), (
                        f"request {r.rid}: restore prefill diverged "
                        "from the emitted stream")
                    rows_cache, tok = self._replay_generated(r,
                                                             rows_cache)
                    row_i = 0
                    if tok is None:     # single emitted token: no replay
                        row_i, tok = i, t0
                    r.total_len += len(r.out_tokens) - 1
                else:
                    # prefill-replay: the prefill covered
                    # prompt+generated[:-1]; its argmax re-derives the
                    # last emitted token
                    assert np.array_equal(t0_host[i, 0],
                                          r.out_tokens[-1]), (
                        f"request {r.rid}: restore prefill diverged "
                        "from the emitted stream")
                    row_i, tok = i, t0
                r.resume = False
                with self._lock:
                    self.stats_restores += 1
                    self._inserts.appendleft((r, rows_cache, row_i, tok))
                    self._pending_prefills -= 1
                continue
            r.t_first = now
            first = t0_host[i, 0]
            if r.needs_host_tokens:
                first = int(first)
            r.out_tokens.append(first)
            stopped = r.needs_host_tokens and self._hit_stop(r)
            if stopped or r.max_new == 1:   # done straight from prefill
                r.stopped = stopped and r.max_new > 1
                if r.stopped:
                    with self._lock:
                        self.stats_stopped_early += 1
                self._finish(r)
                with self._lock:
                    self._pending_prefills -= 1
            else:
                # the decrement shares the lock with the append, so the
                # decode driver can never observe "drained" while a
                # prefilled row is still on its way to a slot
                with self._lock:
                    self._inserts.append((r, rows_cache, i, t0))
                    self._pending_prefills -= 1
        self._work.set()

    def _replay_generated(self, req: Request, rows_cache):
        """Decode-replay restore: re-feed the recorded tokens through the
        serve step on the freshly-prefilled row cache, advancing its
        ``pos`` to prompt+generated — the *same* computation the first
        pass ran, so bit-exact on configs (MoE capacity, SSD chunking,
        SWA rings) where a longer prefill would not be.  Returns the
        advanced cache and the device token feeding the next tick (None
        when only the prefill token was ever emitted).  Every replayed
        argmax must reproduce the recorded stream."""
        toks = req.out_tokens
        if len(toks) <= 1:
            return rows_cache, None
        extra = ((self.cfg.n_codebooks,)
                 if self.cfg.frontend == "audio_codebooks" else ())
        cache, nxts = rows_cache, []
        pins = []               # chain versions + fed tokens: keep refs
        for k in range(len(toks) - 1):
            fed = self.kv.to_dev(
                np.asarray(toks[k]).reshape((1, 1) + extra))
            pins.append((cache, fed))
            nxt, cache = self._dispatch(self.replay, self._params, cache,
                                        fed)
            nxts.append(nxt)
        # one sync for the whole chain (dispatch stays pipelined), then
        # verify every replayed argmax against the recorded stream
        with self._span("prefill.sync", (req.rid,)):
            jax.block_until_ready(cache["pos"])
        pins.clear()
        for k, nxt in enumerate(nxts):
            assert np.array_equal(np.asarray(nxt)[0, 0], toks[k + 1]), (
                f"request {req.rid}: decode replay diverged at "
                f"token {k + 1}")
        return cache, nxts[-1]

    @staticmethod
    def _hit_stop(req: Request, n_new: int = 1) -> bool:
        """Early-stop check on the host-visible emitted stream (only ever
        called for ``needs_host_tokens`` requests, whose ``out_tokens``
        are plain ints).

        ``n_new`` is how many tokens the caller just committed.  A
        multi-token commit (speculative-decode acceptance) can bury an
        EOS or a completed stop sequence *inside* the committed window,
        so every newly committed position is checked in order — not just
        the tail — and ``out_tokens`` is truncated at the first match so
        the emitted stream stays exactly the prefix the one-shot
        tick-by-tick run would have produced.  A stop sequence may
        *start* before the window (earlier tokens already emitted) as
        long as it *ends* on a new position."""
        out = req.out_tokens
        for i in range(len(out) - n_new, len(out)):
            if req.eos_id is not None and out[i] == req.eos_id:
                del out[i + 1:]
                return True
            for s in req.stop or ():
                if i + 1 >= len(s) and out[i + 1 - len(s):i + 1] == s:
                    del out[i + 1:]
                    return True
        return False

    def _materialise_tokens(self, req: Request):
        """Host-ify the request's emitted stream (one stacked
        device->host sync, not one per token) — called at completion and
        at eviction, where the generated prefix feeds the restore prompt.

        ``out_tokens`` holds the *whole* per-tick token array per emitted
        token (host entries — the prefill token, or tokens materialised
        before an earlier eviction — are left alone): slicing the slot
        row happens here, forced immediately.  Never accumulate unforced
        lazy slices of the hot-loop arrays instead — once the backing
        array's last Python reference drops, its buffer can be recycled
        under async dispatch while the slice is still pending, and the
        value read back is whatever the pool wrote there next (token
        corruption; found the hard way, see tests)."""
        out = req.out_tokens
        idx = [i for i, t in enumerate(out) if isinstance(t, jax.Array)]
        if idx:
            # numpy stack, not jnp: an eager jnp.stack compiles once per
            # distinct length (~35ms each) — paid mid-serve, it stalls
            # whole scheduling rounds
            vals = np.stack([np.asarray(out[i])
                             for i in idx])[:, req.slot, 0]
            for j, i in enumerate(idx):
                out[i] = vals[j]

    def _finish(self, req: Request):
        """Complete a request inline; the response *write* — when a sink
        is configured — is its own UMT task so slow consumers never stall
        the decode loop."""
        self._drop_prefix_hold(req)
        self._materialise_tokens(req)
        req.t_done = time.monotonic()
        with self._lock:
            self._n_completed += 1
            self._tokens_out += len(req.out_tokens)
            self._lat_samples.append(req.latency)
            self._ttft_samples.append(req.ttft)
        req.done.set()
        if self.response_sink is not None:
            self.rt.submit(self._respond, req,
                           name=f"serve.respond:{req.rid}")

    def _respond(self, req: Request):
        io.call(self.response_sink, req)      # monitored response write

    # ------------------------------------------------------- decode driver
    def _rebind_tokens(self, new_tokens):
        """Displace the device token row: the old version is an argument
        of a pending dispatch (the decode that produced ``new_tokens``,
        or the next tick), so it is pinned, not dropped."""
        self.kv.pin(self._tokens)
        self._tokens = new_tokens

    def _rebind_active(self):
        """Refresh the device active mask from the host one, pinning the
        displaced version (same rule as :meth:`_rebind_tokens`)."""
        self.kv.pin(self._active_dev)
        self._active_dev = self.kv.to_dev(self._active)

    def _do_inserts(self):
        """Admit prefilled rows into free slots, strictly head-first
        (restores re-enter at the head, everything else FIFO — keeps a
        large request from being starved by smaller ones slipping past
        it).  The head reserves the pages the *policy* asks for —
        the worst case (default) or just the prefill extent (on-demand).
        If the pool cannot cover them, admission *blocks* (the row stays
        queued; nothing is written) until a free — completion or
        eviction — unblocks it; each distinct blocked head counts once in
        ``admission_blocks``."""
        while True:
            free = np.flatnonzero(~self._active)
            if len(free) == 0:
                return
            with self._lock:
                if not self._inserts:
                    return
                entry = self._inserts[0]
            with self._span("insert", (entry[0].rid,)):
                if not self._admit(entry, free):
                    return

    def _admit(self, entry, free) -> bool:
        """Admit the queued row ``entry`` into one of the ``free`` slots;
        False when its admission blocks on free pages."""
        req, rows_cache, row, t0 = entry
        shared: list[int] = []
        # a prefix hit already holds its matched pages — only the
        # uncached remainder is allocated (never zero: at least the last
        # prompt position was recomputed)
        if req.prefix_hold is not None:
            shared = list(req.prefix_hold.pages)
        need = self.pager.pages_for(
            self.policy.admission_tokens(self, req)) - len(shared)
        ids = self._alloc_pages(need)
        if ids is None and shared:
            # a blocked head must hold nothing — the deadlock-freedom
            # argument (a lone live slot can always grow; every eviction
            # strictly frees) breaks if blocked admissions pin pages.
            # Dropping the hold is always sound: the gathered row cache
            # holds the complete prefix content, so the admission falls
            # back to cold-shaped (all pages fresh) — the prefill compute
            # stayed saved, only page dedup is lost.  The dropped pages
            # revert to refcount-0 cached: this very retry's reclaim may
            # harvest them.
            self._drop_prefix_hold(req)
            shared = []
            need = self.pager.pages_for(
                self.policy.admission_tokens(self, req))
            ids = self._alloc_pages(need)
        if ids is None and not self._active.any() \
                and self._drop_queued_holds():
            # nothing live will ever free a page, so what the head lacks
            # was held by rows queued behind it, which only their own
            # admission would release: they fall back to cold-shaped too
            # (sound as above) — retry
            return True
        if ids is None:             # admission blocked on free pages
            if self._blocked_head != req.rid:
                self._blocked_head = req.rid
                self.stats_admission_blocks += 1
            return False
        self._blocked_head = None
        with self._lock:
            # dequeue this row, not the head: a prefill worker may
            # have pushed a restore there (appendleft) since the peek
            i = next(i for i, e in enumerate(self._inserts)
                     if e is entry)
            del self._inserts[i]
        s = int(self.policy.select_slot(self, free))
        assert not self._active[s], \
            f"policy picked a live slot {s} for admission"
        kv = self.kv
        row_dev, slot_dev = kv.to_dev(np.int32(row)), \
            kv.to_dev(np.int32(s))
        # dispatch temporaries the pending insert reads whose Python
        # refs drop when this admission returns: pin until a sync
        kv.pin(rows_cache, t0, row_dev, slot_dev)
        req.pages = shared + ids
        req.prefix_hold = None      # holds adopted as the slot's own
        table_row = kv.bind_slot_pages(s, req.pages, n_shared=len(shared))
        kv.pin(table_row)
        if kv.debug_validate:
            for pid in ids:         # fresh pages must be private
                assert self.pager.refcount(pid) == 1 \
                    and not self.pager.is_cached(pid), (
                    f"freshly allocated page {pid} is shared")
        with self._pool_lock:
            new_cache = self._dispatch(self.insert, kv.cache, rows_cache,
                                       row_dev, slot_dev, table_row)
            # the displaced version was donated to the insert: never pinned
            kv.commit(new_cache)
        if req.t_admit is None:
            req.t_admit = time.monotonic()
            if req.t_submit is not None:    # queued through submit()
                with self._lock:
                    self._admit_wait_sum += req.t_admit - req.t_submit
                    self._n_admitted += 1
        self._rebind_tokens(self._tokens.at[s].set(t0[row]))
        self._active[s] = True
        self._rebind_active()
        self._slot_req[s] = req
        req.slot = s
        self._slot_pos[s] = req.total_len   # next cache write position
        self._admit_seq += 1
        self._slot_seq[s] = self._admit_seq
        # make the admitted prompt's complete pages reusable the
        # moment their content lands: the trie adopts every full
        # page of the written extent (first-wins on runs an earlier
        # admission already cached).  The insert dispatch above is
        # ordered (pool lock + device FIFO) before any gather a
        # concurrent matcher issues, so a hit can never read pages
        # whose content is still in flight.
        if self.prefix is not None and req.patches is None \
                and req.total_len >= self.page_size:
            key = (req.restore_tokens
                   if req.restore_tokens is not None else req.tokens)
            self.prefix.insert(key, req.pages, req.total_len)
        return True

    def _slot_views(self) -> list:
        """Read-only live-slot snapshots for policy decisions."""
        views = []
        for s in np.flatnonzero(self._active):
            s = int(s)
            req = self._slot_req[s]
            views.append(SlotView(
                slot=s, rid=req.rid, admit_seq=int(self._slot_seq[s]),
                pages_held=len(req.pages) if req.pages else 0,
                next_pos=int(self._slot_pos[s]),
                emitted=len(req.out_tokens), budget=req.max_new))
        return views

    def _evict_slot(self, s: int):
        """Preempt a live slot (mechanism; *which* slot is the policy's
        call): force the dispatch chain, bring its generated tokens to
        host, free the slot — and its pages, the unblock a page-starved
        peer is waiting on — then re-enter the request at the head of
        admission via a restore prefill that replays prompt + generated
        (recompute-on-restore).  The caller refreshes the device
        active mask / block table after its batch of evictions."""
        req = self._slot_req[s]
        # same finish-before-free rule as _tick: the sync proves every
        # dispatched computation that reads this slot's pages (or the
        # current block-table mirror) has executed
        jax.block_until_ready(self._tokens)
        self._materialise_tokens(req)
        self.kv.flush(synced=True)
        req.build_restore(self._restore_prefill)
        # the evicted slot's written pages become reuse capital: the
        # restore's own admission (or any same-prefix arrival) re-hits
        # them in the trie instead of recomputing — PR 5's
        # recompute-on-restore now recomputes only what eviction
        # pressure actually reclaimed
        self._prefix_insert_slot(req)
        self._release_slot(s)           # slot + pages free right now
        self.stats_evictions += 1
        with self._lock:
            self._pending_prefills += 1
        self.rt.submit(self._prefill_round, [req],
                       name=f"serve.restore:{req.rid}"
                            f"@{len(req.out_tokens)}")

    def _alloc_pages(self, n: int):
        """Allocate ``n`` pages, letting the policy reclaim idle
        prefix-cache pages (refcount-0 trie leaves) to cover a shortfall
        *before* the block is surfaced — reclaiming idle cached content
        only costs future reuse, where the caller's fallbacks (admission
        block, victim eviction) cost live work."""
        if n <= 0:
            return []
        got = self.pager.alloc(n)
        if got is None and self.prefix is not None:
            deficit = n - self.pager.free_pages
            if deficit > 0:
                self.policy.prefix_evict(self, deficit)
            got = self.pager.alloc(n)
        return got

    def _prefix_insert_slot(self, req: Request):
        """Adopt a finished/evicted slot's complete pages into the trie:
        key = prompt + generated-so-far minus the last token — exactly
        the written cache extent, so the page containing any position a
        restore might still write never enters.  A restored request that
        later re-enters admission re-hits these pages."""
        if self.prefix is None or req.patches is not None \
                or not req.pages:
            return
        toks = np.asarray(req.tokens)
        gen = req.out_tokens[:-1]
        key = toks if not gen else np.concatenate(
            [toks, np.asarray(gen).reshape((len(gen),) + toks.shape[1:])])
        if len(key) >= self.page_size:
            self.prefix.insert(key, req.pages, len(key))

    def _page_faults(self, ahead=None):
        """On-demand growth: extend a live slot's block table as its next
        write position crosses a page boundary.  ``ahead`` (optional,
        (slots,) ints) is the speculative-decode lookahead — this tick's
        verify window writes positions up to ``_slot_pos[s] + ahead[s]``,
        which can cross *several* page boundaries at once (k > page_size);
        the loop simply keeps growing until the whole window is covered.
        Pool exhaustion here is a *block* surfaced to the policy, which
        must unblock it by naming a victim to evict — the freed pages
        re-admit the faulting slot (paper: every monitored block pairs
        with the unblock that releases it).  Under worst-case reservation
        the fault condition never fires — the admission reservation
        covers every position the window can write (the engine clamps
        draft length to the remaining budget) — so this stays one
        comparison per live slot per tick."""
        grown = evicted = False
        ps = self.page_size
        # oldest-first: the default victim rule spares the oldest slot,
        # so walking in admission order lets the head of the line grow
        # before younger slots consume the pages it needs
        order = sorted(np.flatnonzero(self._active),
                       key=lambda x: self._slot_seq[x])
        for s in order:
            s = int(s)
            if not self._active[s]:     # evicted as a victim this pass
                continue
            req = self._slot_req[s]
            need = self._slot_pos[s] + \
                (0 if ahead is None else int(ahead[s]))
            grown_here = 0
            while self._active[s] and len(req.pages) * ps <= need:
                got = self._alloc_pages(1)
                if got is not None:
                    self.kv.grow_slot_pages(s, got, base=len(req.pages))
                    req.pages.extend(got)
                    self.stats_pages_grown += 1
                    grown_here += 1
                    grown = True
                    continue
                victim = self.policy.select_victim(
                    self, self._slot_views(), needy=s)
                if victim is None or not self._active[int(victim)]:
                    raise RuntimeError(
                        f"policy {self.policy.name!r} returned no live "
                        f"victim for page-starved slot {s} — eviction is "
                        "the only unblock for an on-demand fault")
                self._evict_slot(int(victim))
                evicted = True
            if grown_here > 1:
                self.stats_pages_grown_multi += 1
            if not self._active.any():
                break
        if grown or evicted:
            self.kv.sync_table()
        if evicted:
            self._rebind_active()

    def _release_slot(self, s: int):
        """Free a slot and its pages — immediately, so the
        very next admission can reuse them; the slot's table rows are
        re-pointed at the garbage page so the dead slot's frozen-pos
        cache writes land nowhere.  ``release`` (not ``free``): a page
        another slot shares, or the trie owns, survives this slot's
        exit — only refcount-0 uncached pages return to the free list."""
        req = self._slot_req[s]
        self._active[s] = False
        self._slot_req[s] = None
        self.kv.release_slot_pages(s)
        self.pager.release(req.pages)
        req.pages = None

    def _tick(self):
        kv = self.kv
        # pre-dispatch policy window: unforced preemption, then on-demand
        # page faults (both may evict — the tick below only runs over
        # whatever is still live)
        with self._span("tick.policy"):
            if self._policy_may_evict:
                v = self.policy.maybe_evict(self, self._slot_views())
                if v is not None:
                    self._evict_slot(int(v))
                    self._rebind_active()
                    kv.sync_table()
            self._page_faults()
        if not self._active.any():
            return                      # everything evicted: no tick
        if kv.debug_validate and self.prefix is not None:
            # write-privacy invariant: the page a decode tick writes is
            # never shared or trie-owned (only *complete* pages enter
            # the trie; shared pages are always behind the write head)
            for s in np.flatnonzero(self._active):
                pid = int(kv._table[int(s),
                                    int(self._slot_pos[s])
                                    // self.page_size])
                assert pid != GARBAGE_PAGE and \
                    self.pager.refcount(pid) == 1 and \
                    not self.pager.is_cached(pid), (
                    f"slot {int(s)} would decode-write shared/cached "
                    f"page {pid}")
        with self._span("tick.dispatch"), self._pool_lock:
            new_tokens, new_cache = self._dispatch(
                self.decode, self._params, kv.cache, self._tokens,
                self._active_dev, kv.table_dev)
            kv.commit(new_cache)
        self.stats_decode_dispatches += 1
        self._rebind_tokens(new_tokens)
        self._slot_pos[self._active] += 1   # each live slot wrote one pos
        if self.sync_ticks:
            jax.block_until_ready(self._tokens)
        now = time.monotonic()
        if self._last_tick_t is not None:
            with self._lock:    # stats() iterates this deque concurrently
                self._tick_intervals.append(now - self._last_tick_t)
        self._last_tick_t = now
        live = np.flatnonzero(self._active)
        n_live = len(live)
        self.stats_ticks += 1
        self.stats_decode_tokens += n_live
        self.stats_occupancy_sum += n_live / self.slots
        if n_live > self.stats_max_live_slots:
            self.stats_max_live_slots = n_live
        host_toks = None
        if any(self._slot_req[s].needs_host_tokens for s in live):
            host_toks = np.asarray(self._tokens)   # one small sync
        freed = False
        for s in live:
            req = self._slot_req[s]
            stopped = False
            if req.needs_host_tokens:
                req.out_tokens.append(int(host_toks[s, 0]))
                stopped = self._hit_stop(req)
            else:
                # retain the whole tick array (NOT a lazy slice of it —
                # see _finish); one entry per emitted token
                req.out_tokens.append(self._tokens)
            if stopped or len(req.out_tokens) >= req.max_new:
                req.stopped = stopped and len(req.out_tokens) < req.max_new
                if req.stopped:
                    with self._lock:
                        self.stats_stopped_early += 1
                # finish FIRST: its device->host force drains every
                # computation dispatched so far, so by the time the pages
                # are freed and the block table rewritten nothing pending
                # can still read them
                with self._span("tick.finish", (req.rid,)):
                    self._finish(req)
                    self._prefix_insert_slot(req)  # pages -> reuse capital
                    self._release_slot(s)         # slot + pages freed now
                freed = True
        if freed:
            self._rebind_active()
            self.kv.sync_table()
        # freed: a finish forced the chain; sync_ticks / host_toks: this
        # tick's sync did.  Otherwise flush only past the depth cap.
        synced = freed or self.sync_ticks or host_toks is not None
        if synced or kv.pins <= kv.pin_max:
            kv.flush(synced=synced)
        else:
            with self._span("tick.flush"):  # drains at the pin cap
                kv.flush(synced=False)

    def _spec_window(self, req: Request) -> list[int]:
        """Draft a verify window for one live slot: the policy decides
        how hard to speculate, the drafter proposes, the engine clamps to
        its static pad width and to the slot's remaining token budget —
        so the window can never write a position the never-speculating
        run could not (the admission reservation / ``_validate``
        arithmetic stays exact)."""
        k = min(int(self.policy.spec_draft_k(self, req)), self.spec_k,
                req.max_new - len(req.out_tokens) - 1)
        if k <= 0:
            return []
        # host context = original prompt + everything emitted (spec-mode
        # commits are host ints; the prefill token may be a numpy scalar)
        ctx = [int(t) for t in np.asarray(req.tokens).reshape(-1)] \
            + [int(t) for t in req.out_tokens]
        return [int(d) for d in self.drafter.draft(ctx, k)[:k]]

    def _tick_spec(self):
        """One speculative tick: draft per slot, verify the whole pool's
        windows in ONE dispatch, commit each slot's longest agreeing
        draft prefix + the model's correction.  Every tick runs through
        the verify jit — including no-draft ticks (S=1), which compute
        exactly the decode tick (``pos`` is host-authoritative under
        spec, so the decode jit's device-side ``pos + 1`` would go
        stale).  Acceptance is a host decision, so every spec tick syncs
        the argmaxes — the measured trade: the off leg keeps the async
        pipeline, the spec leg buys fewer dispatches per committed token
        (the PASS-gated axis on this dispatch-bound container)."""
        kv = self.kv
        with self._span("tick.policy"):
            drafts = self._spec_policy()
        if not self._active.any():
            return                      # everything evicted: no tick
        live = [int(s) for s in np.flatnonzero(self._active)]
        if kv.debug_validate and self.prefix is not None:
            # write-privacy invariant over the whole window (not just
            # the next position): every page the verify writes must be
            # private to the slot
            for s in live:
                last = self._slot_pos[s] + len(drafts.get(s, ()))
                for lp in range(int(self._slot_pos[s]) // self.page_size,
                                int(last) // self.page_size + 1):
                    pid = int(kv._table[s, lp])
                    assert pid != GARBAGE_PAGE and \
                        self.pager.refcount(pid) == 1 and \
                        not self.pager.is_cached(pid), (
                        f"slot {s} would verify-write shared/cached "
                        f"page {pid}")
        # two static verify shapes: S=1 (nobody drafted) or S=spec_k+1
        s_width = 1 + (self.spec_k if drafts else 0)
        toks = np.zeros((self.slots, s_width), np.int32)
        n_tok = np.zeros((self.slots,), np.int32)   # 0 = dead slot
        for s in live:
            req = self._slot_req[s]
            win = [int(np.asarray(req.out_tokens[-1]).reshape(()))] \
                + drafts.get(s, [])
            toks[s, :len(win)] = win
            n_tok[s] = len(win)
        # dispatch temporaries stay locals until the host sync below
        toks_dev = kv.to_dev(toks)
        pos_dev = kv.to_dev(self._slot_pos.astype(np.int32))
        n_dev = kv.to_dev(n_tok)
        with self._span("tick.dispatch"), self._pool_lock:
            nxt, new_cache = self._dispatch(
                self.verify, self._params, kv.cache, toks_dev, pos_dev,
                n_dev, kv.table_dev)
            kv.commit(new_cache)
        self.stats_decode_dispatches += 1
        host_nxt = np.asarray(nxt)      # forces the dispatch chain
        now = time.monotonic()
        if self._last_tick_t is not None:
            with self._lock:
                self._tick_intervals.append(now - self._last_tick_t)
        self._last_tick_t = now
        n_live = len(live)
        self.stats_ticks += 1
        self.stats_occupancy_sum += n_live / self.slots
        if n_live > self.stats_max_live_slots:
            self.stats_max_live_slots = n_live
        freed = False
        for s in live:
            req = self._slot_req[s]
            d = drafts.get(s, [])
            # longest agreeing prefix: lane j's argmax is the token the
            # model emits after committing the window up to lane j, so
            # draft j+1 is accepted iff it equals argmax j — and the
            # committed tokens are the ARGMAXES (never the drafts),
            # which is the whole bit-identity argument
            m = 0
            while m < len(d) and int(host_nxt[s, m]) == d[m]:
                m += 1
            commit = [int(host_nxt[s, j]) for j in range(m + 1)]
            n_before = len(req.out_tokens)
            req.out_tokens.extend(commit)
            self._slot_pos[s] += len(commit)
            req.spec_drafted += len(d)
            req.spec_accepted += m
            self.stats_spec_drafted += len(d)
            self.stats_spec_accepted += m
            if m < len(d):
                # rejected lanes roll back for free: their cache writes
                # sit past the committed extent, position-masked out of
                # every later read and overwritten by the next window
                self.stats_spec_rollbacks += 1
            stopped = False
            if req.needs_host_tokens:
                # may truncate out_tokens at a stop buried mid-window
                stopped = self._hit_stop(req, n_new=len(commit))
            # count only the tokens the stream keeps (post-truncation),
            # so dispatches_per_token measures emitted, not computed
            self.stats_decode_tokens += len(req.out_tokens) - n_before
            if stopped or len(req.out_tokens) >= req.max_new:
                req.stopped = stopped and len(req.out_tokens) < req.max_new
                if req.stopped:
                    with self._lock:
                        self.stats_stopped_early += 1
                with self._span("tick.finish", (req.rid,)):
                    self._finish(req)
                    self._prefix_insert_slot(req)
                    self._release_slot(s)
                freed = True
        if freed:
            self._rebind_active()
            self.kv.sync_table()
        # the host_nxt sync above proved the whole chain executed
        self.kv.flush(synced=True)

    def _spec_policy(self) -> dict:
        """A speculative tick's policy window: unforced preemption, a
        draft per live slot, then the page faults the drafts' windows
        need.  Returns {slot: draft} over the slots still live."""
        if self._policy_may_evict:
            v = self.policy.maybe_evict(self, self._slot_views())
            if v is not None:
                self._evict_slot(int(v))
                self._rebind_active()
                self.kv.sync_table()
        # draft before the fault pass: on-demand growth must cover the
        # whole verify window, not just the next position — a window can
        # cross several page boundaries at once
        drafts = {}
        for s in np.flatnonzero(self._active):
            s = int(s)
            d = self._spec_window(self._slot_req[s])
            if d:
                drafts[s] = d
        ahead = np.zeros((self.slots,), np.int64)
        for s, d in drafts.items():
            ahead[s] = len(d)
        self._page_faults(ahead=ahead)
        # the fault pass may have evicted a drafted slot
        return {s: d for s, d in drafts.items() if self._active[s]}

    def _drained(self) -> bool:
        with self._lock:
            return (self._intake_done and not self._inserts
                    and self._pending_prefills == 0)

    def _decode_loop(self):
        while True:
            self._do_inserts()
            if self._active.any():
                with self._span("tick"):
                    self._tick_spec() if self.drafter is not None \
                        else self._tick()
                continue
            self._last_tick_t = None     # idle gap: not tick jitter
            if self._drained():
                break
            self._work.clear()
            with self._lock:
                pending = bool(self._inserts)
            if pending:
                continue
            # nothing live: monitored wait frees this core for prefill /
            # weights / intake work (timeout is only a belt-and-braces
            # fallback for the clear/set race above)
            io.wait(self._work, self.idle_wait)

    # ------------------------------------------------------------ reporting
    def stats(self) -> dict:
        """Latency quantiles come from bounded sample windows (the most
        recent 4096 completions / 65536 ticks), counts are exact.  Tick
        intervals measure real compute cadence only with
        ``sync_ticks=True`` (dispatch cadence otherwise)."""
        with self._lock:
            n = self._n_completed
            tokens_out = self._tokens_out
            lats = sorted(self._lat_samples)
            ttfts = sorted(self._ttft_samples)
            ticks = sorted(self._tick_intervals)
            admit_sum, n_admit = self._admit_wait_sum, self._n_admitted
        out = {
            "requests": n,
            "slots": self.slots,
            "ticks": self.stats_ticks,
            "decode_tokens": self.stats_decode_tokens,
            "tokens_out": tokens_out,
            "occupancy": (self.stats_occupancy_sum / self.stats_ticks
                          if self.stats_ticks else 0.0),
            "max_live_slots": self.stats_max_live_slots,
            "prefill_calls": self.stats_prefill_calls,
            "prefill_reqs": self.stats_prefill_reqs,
            "prefill_chunks": self.stats_prefill_chunks,
            "prefill_chunk_tasks": self.stats_prefill_chunk_tasks,
            "stopped_early": self.stats_stopped_early,
            "admission_blocks": self.stats_admission_blocks,
            "evictions": self.stats_evictions,
            "restores": self.stats_restores,
            "pages_grown": self.stats_pages_grown,
            "pages_grown_multi": self.stats_pages_grown_multi,
            "decode_dispatches": self.stats_decode_dispatches,
            "dispatches_per_token": (
                self.stats_decode_dispatches
                / max(self.stats_decode_tokens, 1)),
            "spec": self.spec_mode or "off",
            "spec_k": self.spec_k if self.spec_mode else 0,
            "spec_drafted": self.stats_spec_drafted,
            "spec_accepted": self.stats_spec_accepted,
            "spec_rollbacks": self.stats_spec_rollbacks,
            "spec_accept_rate": (
                self.stats_spec_accepted
                / max(self.stats_spec_drafted, 1)),
            "prefix_cache": self.prefix is not None,
            "prefix_hits": self.stats_prefix_hits,
            "prefix_tokens_saved": self.stats_prefix_tokens_saved,
            "cow_forks": self.stats_cow_forks,
            "policy": self.policy.name,
            "paged_kernel": self.paged_kernel,
            "tp": self.tp,
            "p50_latency_s": percentile(lats, 0.50),
            "p99_latency_s": percentile(lats, 0.99),
            "p50_ttft_s": percentile(ttfts, 0.50),
            "p99_ttft_s": percentile(ttfts, 0.99),
            "p50_tick_s": percentile(ticks, 0.50),
            "p99_tick_s": percentile(ticks, 0.99),
            "page_size": self.page_size,
            # first admission's t_admit - t_submit, over admitted requests
            "admit_wait_mean_s": admit_sum / n_admit if n_admit else None,
        }
        if self.rt.tracer.enabled:
            # seconds of each span's own work, children excluded
            out["span_self_s"] = self.rt.tracer.span_self_s()
        out.update(self.kv.stats())     # versions, commits, pager pool
        if self.prefix is not None:
            out.update(self.prefix.stats())
        return out
