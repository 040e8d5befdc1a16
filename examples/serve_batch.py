"""Serve a small model two ways and compare:

  * ``oneshot`` — prefill one static batch of padded prompts, decode the
    whole batch to completion (the pre-engine path);
  * ``engine``  — continuous batching on the UMT runtime: requests arrive
    over time, are prefilled and inserted into free slots while decode
    keeps running, and finished sequences free their slot immediately.

Any given request's greedy tokens are identical under both paths (the
engine run below serves more requests than the one-shot batch, so the
printed samples differ; tests/test_serve_engine.py asserts the per-request
equivalence).  The engine keeps its slots busy under staggered arrivals
instead of waiting for the whole batch.

    PYTHONPATH=src python examples/serve_batch.py [--arch jamba-v0.1-52b]

The full engine flag surface (``python -m repro.launch.serve``) — every
knob is a layout/scheduling change, never a tokens change (greedy output
is bit-identical across all of them, fuzz-tested):

  * ``--page-size N`` / ``--pages N`` — paged KV cache geometry (0 =
    auto size; ``N`` = the cache length gives each slot one page).
    Paging is the substrate for the three flags below.
  * ``--policy reserve|ondemand`` — worst-case page reservation at
    admission vs on-demand growth with preemption-by-eviction
    (``ondemand`` admits more but may evict + bit-exactly restore).
  * ``--prefix-cache auto|on|off`` — radix-trie reuse of shared prompt
    prefixes over refcounted pages (chunk-exact configs; new requests
    link cached pages and prefill only their tail).
  * ``--paged-kernel`` — decode attention via the fused Pallas kernel
    that walks the block table in-kernel (paged GQA/MLA only; off-TPU it
    runs interpret-mode, a correctness harness not a speed claim).
  * ``--spec ngram --spec-k K`` — speculative decoding: n-gram prompt
    lookup drafts K tokens/slot, one batched dispatch verifies; fewer
    device dispatches per token, same tokens.
  * ``--mesh DATA,MODEL`` — device mesh over the visible devices
    (default 1,N).  With a model axis > 1 the engine serves
    tensor-parallel: KV pool heads and weight fan-out shard, tables
    stay replicated, donation still aliases per shard.  Composes with
    everything above — policy/spec/prefix run host-side against the
    same block tables, the paged kernel dispatches per-shard — and the
    stats line reports ``"tp": true``.  Off-accelerator, force devices
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
  * ``--chunk N`` — Sarathi-style chunked prefill (bounds decode-tick
    jitter under long prompts); ``--no-umt`` — baseline runtime where a
    blocked core idles (the paper's A/B).
"""
import argparse

from repro.launch.serve import serve

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="jamba-v0.1-52b",
                help="any assigned architecture (tiny variant is used)")
args = ap.parse_args()

common = ["--arch", args.arch, "--tiny", "--batch", "4",
          "--prompt-len", "24", "--gen", "12"]
serve(common + ["--mode", "oneshot"])
serve(common + ["--mode", "engine", "--requests", "8",
                "--arrival-ms", "20"])
