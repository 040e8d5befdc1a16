"""Smoke test of the serving path on a TPU: one run proves the system
still starts on the chip and serves correct tokens.

    python chip_smoke.py              # one chip: minicpm3-4b
    python chip_smoke.py --chips 4    # four chips: qwen2.5-14b, TP (1, 4)

It goes through the functions ``python -m repro.launch.serve`` uses —
the served config (weights in the compute dtype, random from ``--seed``,
created where they live), the (data, model) mesh over the visible
devices, and ``ServeEngine`` over a paged KV cache — at the model's
published widths, and serves every request twice: through the
dense-gather decode leg and through the paged-attention kernel leg.
Each leg is checked against ``steps.greedy_oneshot`` on the same
prompts; the kernel leg's compiled decode must contain the Mosaic kernel
(``tpu_custom_call``), and the kernel's output on the live KV pool must
match ``kernels/paged_attention/ref.py``.

Earlier lines are smoke readings (wall times that include compilation,
tokens/s, peak device bytes), not metrics.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed.  Without a TPU, or without the repository around it, the script
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

# served shapes per phase: every request fits its slot (prompt + gen <=
# cache_len) and page_size is a multiple of bf16's 16-row tile
ONE_CHIP = dict(arch="minicpm3-4b", requests=8, prompt_len=256, gen=32,
                slots=8, cache_len=512, page_size=16)
FOUR_CHIP = dict(arch="qwen2.5-14b", requests=4, prompt_len=128, gen=16,
                 slots=4, cache_len=256, page_size=16)

# The gather leg runs the reference's own decode math, so its tokens must
# equal greedy_oneshot's exactly.  The kernel leg keeps scores and
# probabilities in f32 where the gather path rounds them to bf16, and a
# deep random-weight model amplifies such rounding until near-tied
# logits flip, after which a stream follows its own context.  So the
# kernel leg is held to a logit tolerance instead: fed the served
# stream, the reference's top logit may exceed its logit for the served
# token by at most LOGIT_TOL at every position.  The random weights
# (std 0.02 over d_model >= 2560, after an RMSNorm) give logits of about
# unit spread, so the top token sits about 4 above the vocabulary's
# median and its runner-up typically ~0.2 below it: rounding flips such
# near-ties, while a token the reference would not consider lies units
# down.  The run prints the median top-minus-median spread beside it.
LOGIT_TOL = 0.5
# Kernel vs ref.py on one layer's live pool: both read the same bf16
# pages and accumulate in f32; the outputs (|x| <~ 4) are rounded to
# bf16 (a step of 2**-6 at [2, 4)), and the kernel's f32 dots may run as
# bf16 MXU passes.  The reference runs at highest matmul precision.
ATTN_TOL = 3e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects failures so one run reports every phase."""

    def __init__(self):
        self.failures: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        log(("ok    " if ok else "FAIL  ") + what)
        if not ok:
            self.failures.append(what)

    def phase(self, name, fn, *args):
        t0 = time.perf_counter()
        try:
            return fn(*args)
        except Exception:                       # noqa: BLE001 — reported
            traceback.print_exc()
            self.expect(False, f"{name}: raised")
            return None
        finally:
            log(f"smoke reading: {name} wall "
                f"{time.perf_counter() - t0:.3f} s (compilation included)")


def serve_leg(cfg, params, mesh, prompts, shape, paged_kernel, checks):
    """Serve every prompt through ``ServeEngine``; returns the engine and
    the (requests, gen) token array."""
    from repro.launch.serve import check_requests
    from repro.serve import Request, ServeEngine

    leg = "kernel" if paged_kernel else "gather"
    eng = ServeEngine(cfg, params, slots=shape["slots"],
                      cache_len=shape["cache_len"], mesh=mesh,
                      page_size=shape["page_size"],
                      paged_kernel=paged_kernel)
    reqs = [Request(i, prompts[i], max_new_tokens=shape["gen"])
            for i in range(len(prompts))]
    for r in reqs:                  # queued before start: one prefill round
        eng.submit(r)
    t0 = time.perf_counter()
    with eng:
        eng.close()
        eng.join()
    wall = time.perf_counter() - t0
    check_requests(reqs)
    counts = [len(r.out_tokens) for r in reqs]
    checks.expect(all(c == shape["gen"] for c in counts),
                  f"{leg} leg: every request emitted {shape['gen']} tokens "
                  f"(got {sorted(set(counts))})")
    stats = eng.stats()
    log(f"smoke reading: {leg} leg served {stats['tokens_out']} tokens in "
        f"{wall:.3f} s, {stats['tokens_out'] / wall:.1f} tokens/s "
        "(compilation included)")
    return eng, np.stack([np.asarray(r.out_tokens, np.int32).reshape(-1)
                          for r in reqs])


def forced_gaps(prefill, logit_step, params, prompts, tokens):
    """Feed ``tokens`` (B, gen) through the reference path.  Returns, per
    position, its top logit minus its logit for the fed token (0 where
    the reference would have chosen it too), and its top logit minus its
    median logit."""
    gaps, spreads = [], []

    def read(logits, tok):
        lg = np.asarray(logits[:, 0], np.float32)
        top = lg.max(-1)
        gaps.append(top - lg[np.arange(len(tok)), tok])
        spreads.append(top - np.median(lg, axis=-1))

    cache, logits = prefill(params, jnp.asarray(prompts), None)
    read(logits, tokens[:, 0])
    for t in range(1, tokens.shape[1]):
        logits, cache = logit_step(params, cache,
                                   jnp.asarray(tokens[:, t - 1:t]))
        read(logits, tokens[:, t])
    return np.stack(gaps, axis=1), np.stack(spreads, axis=1)


def compare_tokens(leg, got, ref, gaps_of, checks):
    """Compare a leg's tokens with greedy_oneshot's.  Where they differ,
    print the first divergence and, with the reference fed the served
    streams, the largest logit gap of a served token.  The gather leg
    must not differ at all; the kernel leg must keep every gap within
    LOGIT_TOL."""
    same = np.array_equal(got, ref)
    if not same:
        bad = np.argwhere(got != ref)
        r, t = bad[np.argmin(bad[:, 1])]
        gaps, spreads = gaps_of(got)
        log(f"{leg} leg: tokens differ from greedy_oneshot at {len(bad)} "
            f"of {got.size} positions; first divergence request {r} "
            f"position {t} (served {got[r, t]}, reference {ref[r, t]}); "
            f"fed the served streams, the reference's largest logit gap "
            f"to a served token is {gaps.max():.6g} and it agrees at "
            f"{float(np.mean(gaps == 0)):.4f} of positions; its median "
            f"top-minus-median logit spread is "
            f"{float(np.median(spreads)):.6g}")
    if leg == "gather" or same:
        checks.expect(same, f"{leg} leg: tokens equal greedy_oneshot's "
                            f"({got.size} tokens)")
    else:
        checks.expect(gaps.max() <= LOGIT_TOL,
                      f"{leg} leg: every served token within {LOGIT_TOL} "
                      "of the reference's top logit")


def kernel_in_decode(eng, checks):
    kv = eng.kv
    txt = eng.decode.lower(eng._params, kv.cache, eng._tokens,
                           eng._active_dev, kv.table_dev).compile().as_text()
    checks.expect("tpu_custom_call" in txt,
                  "kernel leg: compiled decode contains tpu_custom_call")


def kernel_matches_ref(cfg, eng, shape, seed, checks):
    """Run the paged kernel and ref.py on layer 0 of the kernel leg's KV
    pool (the values the model wrote), with seeded queries and block
    tables over every page."""
    from repro.kernels.paged_attention import ops, ref

    block = eng.kv.cache["blocks"][0]
    b, ps = shape["slots"], shape["page_size"]
    pps = shape["cache_len"] // ps
    n_pages = next(iter(block.values())).shape[1]
    table = (1 + np.arange(b * pps) % (n_pages - 1)).reshape(b, pps)
    pos = np.linspace(0, shape["cache_len"] - 1, b).astype(np.int32)
    kq = jax.random.split(jax.random.PRNGKey(seed + 7), 2)
    # gather each leaf to one device: the check is of the kernel, not of
    # the engine's layout
    pools = {k: jnp.asarray(np.asarray(v[0])) for k, v in block.items()}
    table, pos = jnp.asarray(table, jnp.int32), jnp.asarray(pos)
    dt = jnp.dtype(cfg.dtype)
    if cfg.pattern[0].attn == "mla":
        h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
        scale = (dn + dr) ** -0.5
        q_lat = jax.random.normal(kq[0], (b, 1, h, cfg.kv_lora_rank), dt)
        q_rope = jax.random.normal(kq[1], (b, 1, h, dr), dt)
        args = (q_lat, q_rope, pools["ckv"], pools["krope"], table, pos)
        got = ops.paged_mla_decode_attention(*args, page_size=ps,
                                             scale=scale)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_mla_decode_attention_ref(*args, page_size=ps,
                                                      scale=scale)
    else:
        q = jax.random.normal(kq[0], (b, 1, cfg.n_heads, cfg.head_dim), dt)
        args = (q, pools["k"], pools["v"], table, pos)
        got = ops.paged_decode_attention(*args, page_size=ps)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_decode_attention_ref(*args, page_size=ps)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.abs(got - want).max())
    log(f"kernel vs ref.py on the live pool: max |diff| {err:.6g}, max "
        f"|ref| {float(np.abs(want).max()):.6g}")
    checks.expect(np.isfinite(got).all() and err <= ATTN_TOL,
                  f"paged kernel matches ref.py within {ATTN_TOL}")


def smoke(shape, seed, checks):
    """Serve ``shape`` on every visible device; returns the device info."""
    from repro.launch.mesh import make_mesh_from_devices
    from repro.launch.serve import device_info, init_serve_params, \
        serve_config
    from repro.serve import make_jit_steps
    from repro.steps import greedy_oneshot, make_serve_step

    cfg = serve_config(shape["arch"])
    mesh = make_mesh_from_devices()
    n_dev = jax.device_count()
    tp = n_dev > 1                  # the engine's own rule: model axis > 1
    log(f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_layers} layers, "
        f"weights {cfg.param_dtype}, mesh {dict(mesh.shape)}")
    params = checks.phase("init weights", lambda: jax.block_until_ready(
        init_serve_params(cfg, mesh, seed)))
    if params is None:
        return device_info()
    leaves = jax.tree.leaves(params)
    total = sum(x.nbytes for x in leaves)
    per_dev = {}
    for x in leaves:
        for s in x.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + \
                s.data.nbytes
    log(f"weights: {total} bytes; per device {per_dev}")
    if tp:
        checks.expect(max(per_dev.values()) < total / 2,
                      "weights are sharded: no device holds half of them")

    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (shape["requests"],
                                       shape["prompt_len"]), 0, cfg.vocab))
    legs = {}
    for paged_kernel in (False, True):
        name = "kernel" if paged_kernel else "gather"
        legs[name] = checks.phase(
            f"{name} leg", serve_leg, cfg, params, mesh, prompts, shape,
            paged_kernel, checks)

    # the one-shot reference on the same mesh, laid out like the engine
    steps = make_jit_steps(cfg, mesh, shape["cache_len"], tp=tp)
    serve_step = jax.jit(make_serve_step(cfg, mesh, tp=tp))
    ref = checks.phase("greedy_oneshot", lambda: np.asarray(greedy_oneshot(
        steps["prefill"], serve_step, params, jnp.asarray(prompts), None,
        shape["gen"])))
    logit_step = jax.jit(make_serve_step(cfg, mesh, tp=tp,
                                         return_logits=True))

    def gaps_of(tokens):
        return forced_gaps(steps["prefill"], logit_step, params, prompts,
                           tokens)

    for name, out in legs.items():
        if out is None or ref is None:
            continue
        eng, tokens = out
        checks.phase(f"{name} leg vs greedy_oneshot", compare_tokens, name,
                     tokens, ref, gaps_of, checks)
        if name == "kernel":
            checks.phase("kernel leg HLO", kernel_in_decode, eng, checks)
            checks.phase("kernel vs ref.py", kernel_matches_ref, cfg, eng,
                         shape, seed, checks)
        if tp:
            checks.phase(f"{name} leg pool layout", pool_is_sharded, eng,
                         n_dev, checks)
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        log(f"smoke reading: device {d.id} peak bytes in use "
            f"{stats.get('peak_bytes_in_use')}")
    return device_info()


def pool_is_sharded(eng, n_dev, checks):
    """Every KV pool leaf with a head dim holds 1/n_dev of its bytes on
    each device, one shard per device."""
    for i, block in enumerate(eng.kv.cache["blocks"]):
        for name, leaf in block.items():
            shards = leaf.addressable_shards
            per = [s.data.nbytes for s in shards]
            log(f"pool block {i} leaf {name}: {leaf.nbytes} bytes; per "
                f"device {per}")
            checks.expect(
                len({s.device.id for s in shards}) == n_dev
                and all(p * n_dev == leaf.nbytes for p in per),
                f"pool leaf {name} holds 1/{n_dev} of its bytes on each "
                "device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: minicpm3-4b on one chip; 4: qwen2.5-14b "
                         "tensor-parallel on a (1, 4) mesh, and nothing "
                         "else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    if jax.device_count() != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{jax.device_count()} devices", file=sys.stderr)
        return 1
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repository's sources are not at {SRC}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    checks = Checks()
    shape = ONE_CHIP if args.chips == 1 else FOUR_CHIP
    dev = smoke(shape, args.seed, checks)
    if checks.failures:
        print("chip_smoke: FAILED: " + "; ".join(checks.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
