"""``repro.serve`` — continuous-batching serve engine on the UMT runtime.

Why this lives on UMT (paper mapping)
-------------------------------------
The paper's thesis is that a thread blocked in the kernel should not idle
its core: a runtime *notified* of block/unblock events (per-core eventfd
channels, §III) schedules other ready work there.  Serving is the one
workload in this repo that is naturally I/O-driven, and it maps onto the
paper's model one-to-one:

  =====================  ==========================================
  serving event          paper's block/unblock model
  =====================  ==========================================
  request wait           monitored block (``io.wait`` on the queue)
  request arrival        unblock -> eventfd wake, Leader reschedules
  response write         monitored block (``io.call`` on the sink)
  idle slot pool         decode task blocks; core runs prefill
  weights load           monitored file reads overlap request wait
  =====================  ==========================================

So a worker blocked on request arrival idles no core — the runtime runs
prefill, decode ticks, response writes, or checkpointed-weights loading
there instead.  With ``umt=False`` the same task graph runs on the
baseline runtime (blocked worker = idle core), which is exactly the
engine-level A/B that ``benchmarks/serve.py`` measures.

Continuous batching
-------------------
A fixed pool of ``slots`` sequences shares one batched KV cache whose
``pos`` is per-slot.  Finished sequences free their slot immediately; new
prompts are prefilled (coalesced per arrival round into one *batched*
call per prompt shape, and — with ``prefill_chunk`` — split into bounded
cache-append chunks so decode ticks interleave) and *inserted* into free
slots while decode keeps ticking over live slots (``make_decode_step``,
active-slot masked).  Greedy outputs are bit-identical to the one-shot
serve path for any arrival order and slot schedule (tested).

Single-owner KV state & buffer donation
---------------------------------------
The cache pytree has exactly one owner — :class:`repro.serve.kvstate.
KVState`, held by the engine — and every cache-updating jit (decode,
verify, insert, chunk) *donates* it (``donate_argnums`` on the cache
argument): XLA aliases every cache leaf in place, so a decode tick
updates the KV pool without materialising a copy.  Every rebind of the
live version goes through ``KVState.commit``.  Versioned pinning keeps
any other buffer a dispatched-but-pending computation still reads alive
(this backend can recycle such buffers — see
``examples/repro_buffer_lifetime.py``) and is exclusive with donation: a
donated version is consumed by the computation that produced its
successor and is never pinned.

Mechanism/policy split
----------------------
Scheduling *decisions* — admit or defer, prefill batch composition,
chunk boundaries, slot placement, evict/restore — live in one
replaceable layer (:mod:`repro.serve.policy`); the engine, KV state and
pager keep only *mechanism* (task graph, donation/pinning, block tables,
free list).  This mirrors the paper's own split (kernel mechanism,
user-space runtime policy) one level up.

Paged KV cache
--------------
The linear attention cache leaves are paged (vLLM-style): physical pages
of ``page_size`` token slots allocated from a free list
(:class:`repro.serve.pager.PagePool`) at admission and freed the moment a
request finishes (including early ``eos_id``/``stop`` stops), addressed
through per-slot block tables.  KV memory is bounded by live tokens
rather than ``slots * cache_len``, so at equal memory the pool runs
strictly more concurrent slots than one page per slot
(``page_size == cache_len``) would.  Bounded leaves (SWA rings, SSM
conv/state) keep one row per slot inside the same cache.  The default
policy reserves the worst case at admission, which *blocks* on pool
exhaustion, deadlock-free; ``policy="ondemand"`` reserves only the
prefill extent and grows a slot's block table as decode crosses page
boundaries — at equal memory it sustains strictly more live slots, and
exhaustion mid-decode is unblocked by preemption: the policy's victim is
evicted and restored later by replaying prefill over prompt + generated
tokens (recompute-on-restore, bit-exact — tested).  Page reuse across
slots can never corrupt: dead slots' tables point at the reserved
garbage page 0.

Shared-prefix KV reuse (radix prefix cache)
-------------------------------------------
On top of the pager's per-page refcounts, a radix trie
(:class:`repro.serve.prefix.PrefixCache`) keyed on full-page token runs
maps prompt prefixes to the physical pages that already hold their K/V.
Admission matches a prompt's longest cached prefix, points the new
slot's block table at the *shared* pages (refcount +1 each; the paged
decode kernel reads them unchanged), and prefills **only the uncached
tail** — a gather step copies the matched content into the slot's row
cache and the chunked-prefill machinery appends from the divergence
position, bit-identical to a cold prefill (the ``chunkable`` gate is
exactly the extent-invariance this needs; MoE/SSM/short-SWA configs
bypass transparently).  A partially-matched divergence page is forked
copy-on-write: its content rides the same gather, the fork lands on a
fresh private page, and the source is never written — the donated
insert's write path sees the garbage page wherever the table holds a
shared id.  Finished and evicted slots donate their complete pages to
the trie (refcount 0, still allocated: idle reuse capital); reclaim is
LRU over refcount-0 leaves, surfaced to the policy
(``SchedulerPolicy.prefix_evict``) before an allocation shortfall
becomes an admission block or a preemption.  ``prefix_cache="off"`` is
the benchmark A/B leg.

Usage
-----
::

    from repro.configs import get
    from repro.models.lm import init_params
    from repro.serve import Request, ServeEngine

    cfg = get("qwen2.5-14b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    with ServeEngine(cfg, params, slots=4, cache_len=48) as eng:
        reqs = [Request(i, prompt_i, max_new_tokens=16) for i in ...]
        for r in reqs:
            eng.submit(r)        # any time, from any thread
        eng.close()              # no more arrivals
        eng.join()               # drain
    print(eng.stats())           # tokens/s inputs, occupancy, p50/p99

The CLI front-end is ``python -m repro.launch.serve --mode engine``
(``--mode oneshot`` keeps the pre-engine one-shot batch path for
comparison); the load benchmark is ``python -m benchmarks.serve``.
"""
from .engine import (ServeEngine, auto_page_size, make_jit_steps,
                     param_shardings)
from .kvstate import KVState, alias_safe
from .pager import GARBAGE_PAGE, PagePool
from .policy import (POLICIES, OnDemandPolicy, SchedulerPolicy, SlotView,
                     make_policy)
from .prefix import PrefixCache, PrefixMatch
from .request import Request, RequestQueue

__all__ = ["ServeEngine", "Request", "RequestQueue", "make_jit_steps",
           "KVState", "alias_safe", "PagePool", "GARBAGE_PAGE",
           "auto_page_size", "SchedulerPolicy", "OnDemandPolicy",
           "SlotView", "make_policy", "POLICIES", "PrefixCache",
           "PrefixMatch", "param_shardings"]
