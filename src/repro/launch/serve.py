"""Serving driver — thin CLI over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --tiny \
        --slots 4 --requests 8 --prompt-len 32 --gen 16

``--mode engine`` (default) runs ``repro.serve.ServeEngine``: requests
flow through the monitored queue, prefill/insert/decode/respond run as
UMT tasks, finished slots free immediately.  ``--mode oneshot`` keeps the
pre-engine behaviour — prefill one static batch, decode it to completion —
as the comparison baseline (same greedy tokens, tested).

Weights are random (seeded) and held in the compute dtype; the JSON line
names the device the run used.  A request that fails inside the engine
fails the command.
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get
from ..models.lm import init_params
from ..serve import param_shardings
from ..steps import make_prefill_step, make_serve_step
from .compile_cache import use_compile_cache
from .mesh import make_mesh_from_devices


def serve_config(arch: str, tiny: bool = False):
    """The served config: weights in the compute dtype (float32 master
    weights are a training concern; ``.tiny()`` configs compute in
    float32, so they keep float32 weights)."""
    cfg = get(arch)
    if tiny:
        cfg = cfg.tiny()
    return cfg.replace(param_dtype=cfg.dtype)


def init_serve_params(cfg, mesh, seed: int = 0):
    """Random weights from ``seed``, created where they will live: on a
    mesh with a model axis each device initialises only its own shard
    (``param_shardings``), so no device ever holds the whole model."""
    tp = mesh is not None and mesh.shape.get("model", 1) > 1
    init = jax.jit(functools.partial(init_params, cfg),
                   out_shardings=param_shardings(cfg, mesh) if tp else None)
    return init(jax.random.PRNGKey(seed))


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def check_requests(reqs) -> None:
    """Raise if any request failed inside the engine (``Request.error``)
    — a failed prefill or decode must never end in a result line."""
    failed = [r for r in reqs if r.error is not None]
    if failed:
        r = failed[0]
        raise RuntimeError(
            f"{len(failed)} of {len(reqs)} requests failed; request "
            f"{r.rid}: {r.error!r}") from r.error


def _prompts(cfg, batch, prompt_len, seed=1):
    shp = (batch, prompt_len)
    if cfg.frontend == "audio_codebooks":
        shp = shp + (cfg.n_codebooks,)
    prompts = jax.random.randint(jax.random.PRNGKey(seed), shp, 0, cfg.vocab)
    patches = None
    if cfg.frontend == "vision_patches":
        patches = jnp.zeros((batch, cfg.n_patches, cfg.d_model),
                            jnp.dtype(cfg.dtype))
    return prompts, patches


def _cache_len(cfg, prompt_len, gen):
    return prompt_len + gen + (
        cfg.n_patches if cfg.frontend == "vision_patches" else 0)


def serve_oneshot(cfg, params, mesh, args):
    """Pre-engine path: prefill one batch, decode greedily to the end."""
    cache_len = _cache_len(cfg, args.prompt_len, args.gen)
    prefill = jax.jit(make_prefill_step(cfg, mesh, cache_len=cache_len))
    decode = jax.jit(make_serve_step(cfg, mesh), donate_argnums=(1,))
    prompts, patches = _prompts(cfg, args.batch, args.prompt_len)

    t0 = time.time()
    cache, last_logits = prefill(params, prompts, patches)
    t_prefill = time.time() - t0

    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        tok, cache = decode(params, cache, tok)
        out.append(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out, axis=1)
    print(json.dumps({
        "mode": "oneshot",
        "arch": cfg.name,
        "device": device_info(),
        "prefill_s": round(t_prefill, 3),
        "decode_s_per_tok": round(t_decode / max(args.gen - 1, 1), 4),
        "generated_shape": list(gen.shape),
        "sample": [int(x) for x in jnp.ravel(gen)[:8]],
    }))
    return gen


def serve_engine(cfg, params, mesh, args):
    """Continuous batching: a slot pool fed by a monitored request queue."""
    from ..serve import Request, ServeEngine

    cache_len = _cache_len(cfg, args.prompt_len, args.gen)
    prompts, patches = _prompts(cfg, args.requests, args.prompt_len)
    prompts = np.asarray(prompts)

    t0 = time.time()
    with ServeEngine(cfg, params, slots=args.batch, cache_len=cache_len,
                     mesh=mesh, umt=not args.no_umt, n_cores=args.cores,
                     page_size=args.page_size or "auto",
                     num_pages=args.pages if args.pages > 0 else None,
                     prefill_chunk=args.chunk if args.chunk > 0
                     else None,
                     paged_kernel=args.paged_kernel,
                     policy=args.policy,
                     prefix_cache=args.prefix_cache,
                     spec=None if args.spec == "off" else args.spec,
                     spec_k=args.spec_k) as eng:
        reqs = []
        for i in range(args.requests):
            reqs.append(Request(
                i, prompts[i],
                patches=None if patches is None else np.asarray(patches[i]),
                max_new_tokens=args.gen))
            eng.submit(reqs[-1])
            if args.arrival_ms:
                time.sleep(args.arrival_ms / 1e3)
        eng.close()
        eng.join()
        stats = eng.stats()
    wall = time.time() - t0
    check_requests(reqs)

    gen = jnp.asarray(np.stack(
        [np.asarray(r.out_tokens, np.int32) for r in reqs]))
    print(json.dumps({
        "mode": "engine",
        "arch": cfg.name,
        "device": device_info(),
        "umt": not args.no_umt,
        "page_size": stats["page_size"],
        "tp": stats["tp"],
        "paged_kernel": stats["paged_kernel"],
        "policy": stats["policy"],
        "kv_versions": stats["kv_version"],
        "pages_used_peak": stats.get("pages_used_peak"),
        "pages_grown": stats["pages_grown"],
        "admission_blocks": stats["admission_blocks"],
        "evictions": stats["evictions"],
        "restores": stats["restores"],
        "prefix_cache": stats["prefix_cache"],
        "prefix_hits": stats["prefix_hits"],
        "prefix_tokens_saved": stats["prefix_tokens_saved"],
        "cow_forks": stats["cow_forks"],
        "shared_pages": stats.get("shared_pages"),
        "pages_cached": stats.get("pages_cached"),
        "prefill_calls": stats["prefill_calls"],
        "prefill_chunks": stats["prefill_chunks"],
        "spec": stats["spec"],
        "spec_drafted": stats["spec_drafted"],
        "spec_accepted": stats["spec_accepted"],
        "spec_rollbacks": stats["spec_rollbacks"],
        "spec_accept_rate": round(stats["spec_accept_rate"], 3),
        "decode_dispatches": stats["decode_dispatches"],
        "dispatches_per_token": round(stats["dispatches_per_token"], 4),
        "wall_s": round(wall, 3),
        "tokens_s": round(stats["tokens_out"] / wall, 1),
        "occupancy": round(stats["occupancy"], 3),
        "p50_latency_s": round(stats["p50_latency_s"], 4),
        "p99_latency_s": round(stats["p99_latency_s"], 4),
        "admit_wait_mean_s": stats["admit_wait_mean_s"],
        "generated_shape": list(gen.shape),
        "sample": [int(x) for x in jnp.ravel(gen)[:8]],
    }))
    return gen


def serve(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--mode", choices=("engine", "oneshot"),
                    default="engine")
    ap.add_argument("--batch", type=int, default=4,
                    help="slot-pool size (engine) / batch size (oneshot)")
    ap.add_argument("--requests", type=int, default=0,
                    help="engine: total requests to serve (default: batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--arrival-ms", type=float, default=0.0,
                    help="engine: gap between request arrivals")
    ap.add_argument("--no-umt", action="store_true",
                    help="engine: baseline runtime (blocked = idle core)")
    ap.add_argument("--cores", type=int, default=None,
                    help="engine: runtime core count")
    ap.add_argument("--page-size", type=int, default=0,
                    help="engine: KV page size, a divisor of the cache "
                         "length (0 = auto)")
    ap.add_argument("--pages", type=int, default=0,
                    help="engine: KV page-pool size incl. garbage page "
                         "(0 = one full cache per slot)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="engine: chunked prefill — prompts longer than "
                         "this prefill as cache-append chunks (0 = off)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="engine: decode attention through the fused "
                         "paged-attention Pallas kernel (reads KV pages "
                         "in place; default is the gather+dense leg)")
    ap.add_argument("--policy", choices=("reserve", "ondemand"),
                    default="reserve",
                    help="engine: scheduler policy — worst-case page "
                         "reservation at admission, or on-demand paging "
                         "with preemption-by-eviction")
    ap.add_argument("--spec", choices=("off", "ngram"), default="off",
                    help="engine: speculative decoding — draft k tokens "
                         "per slot (n-gram prompt lookup, no second "
                         "model) and verify them in one batched "
                         "dispatch; greedy tokens are bit-identical to "
                         "--spec off by construction, only "
                         "dispatches-per-token changes (see "
                         "spec_drafted/spec_accepted/spec_rollbacks in "
                         "the stats line)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="engine: draft window length per slot per tick "
                         "(speculation depth; --spec only)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="device mesh shape over the visible devices "
                         "(default: 1,N — every device on the model "
                         "axis, tensor-parallel serving; the sizes must "
                         "multiply to the device count)")
    ap.add_argument("--prefix-cache", choices=("auto", "on", "off"),
                    default="auto",
                    help="engine: shared-prefix KV reuse (radix cache "
                         "over refcounted pages).  auto enables it on "
                         "chunk-exact configs; off is the A/B "
                         "leg; on fails loudly if the config cannot be "
                         "bit-exact")
    args = ap.parse_args(argv)
    if args.page_size < 0:
        ap.error("--page-size must be 0 (auto) or a positive page size")
    if args.requests <= 0:
        args.requests = args.batch

    cfg = serve_config(args.arch, args.tiny)
    # always mesh over whatever is visible: one device gives the (1, 1)
    # host mesh (annotations present, no sharding), several give (1, n)
    # — the engine auto-enables tensor-parallel serving on the model
    # axis (the old `device_count == 1` special case left multi-device
    # runs with no mesh at all, so they never sharded anything)
    mesh = make_mesh_from_devices(args.mesh)
    params = init_serve_params(cfg, mesh)

    if args.mode == "oneshot":
        return serve_oneshot(cfg, params, mesh, args)
    return serve_engine(cfg, params, mesh, args)


if __name__ == "__main__":
    use_compile_cache()
    serve()
