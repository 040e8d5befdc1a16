"""Compile a cell's decode, insert and largest prefill programs for a
described TPU v5e (``v5e:2x2``), without a chip, and print each one's
``memory_analysis()`` and compile time.  This sizes ``slots`` and
``max_prefill_batch`` before any chip time is spent.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload NAME

Nothing runs: the numbers are bytes and shapes, never times of the chip.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prefill-batch", type=int, default=None,
                    help="prefill batch to compile (default: the cell's "
                         "largest bucket)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import harness
    from repro.launch.mesh import make_mesh
    from repro.models.lm import init_cache, meta_shape_structs, param_meta
    from repro.serve import make_jit_steps, param_shardings
    from repro.serve.engine import auto_page_size
    from repro.serve.kvstate import cache_tree_shardings
    from repro.steps import init_paged_slot_cache

    jax.config.update("jax_enable_compilation_cache", False)
    layout = harness.Layout()
    w = layout.workload(args.workload)
    conf = layout.config(w["config"])
    mix = layout.traffic(w["traffic"])
    sv = conf["serve"]
    cfg = harness.program_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh(conf["mesh"], ("data", "model"),
                     devices=topo.devices[:w["chips"]])
    tp = conf["mesh"][-1] > 1
    rep = NamedSharding(mesh, P())
    dt = jnp.dtype(cfg.dtype)
    slots, cache_len = sv["slots"], sv["cache_len"]
    ps = auto_page_size(cache_len)
    pages = sv.get("num_pages") or slots * cache_len // ps + 1

    def structs(tree, shardings):
        if shardings is None:
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=rep), tree)
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    pshapes = meta_shape_structs(param_meta(cfg), dt)
    params = structs(pshapes, param_shardings(cfg, mesh) if tp else None)
    pool = jax.eval_shape(lambda: init_paged_slot_cache(
        cfg, slots, cache_len, dt, ps, pages))
    pool = structs(pool, cache_tree_shardings(pool, mesh) if tp else None)
    steps = make_jit_steps(cfg, mesh, cache_len, page_size=ps, tp=tp)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    cap = min(sv.get("max_prefill_batch") or slots, slots)
    bmax = args.prefill_batch or 1 << (cap - 1).bit_length()
    lmax = max(layout.generator(mix["kind"]).Traffic(
        mix, 0, 1.0, conf["vocab_size"], slots).prompt_lengths())
    rows = jax.eval_shape(lambda: init_cache(cfg, bmax, cache_len, dt))
    rows = structs(rows, cache_tree_shardings(rows, mesh) if tp else None)
    jobs = {
        "decode": (steps["decode"], (params, pool, i32(slots, 1),
                                     jax.ShapeDtypeStruct((slots,), bool,
                                                          sharding=rep),
                                     i32(slots, cache_len // ps))),
        f"prefill {bmax}x{lmax}": (steps["prefill"],
                                   (params, i32(bmax, lmax), None)),
        f"insert {bmax} rows": (steps["insert"],
                                (pool, rows, i32(), i32(),
                                 i32(cache_len // ps))),
    }
    out = {"workload": args.workload, "page_size": ps, "num_pages": pages}
    for name, (fn, a) in jobs.items():
        t0 = time.perf_counter()
        c = fn.lower(*a).compile()
        m = c.memory_analysis()
        txt = c.as_text()
        out[name] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "code_bytes": m.generated_code_size_in_bytes,
            "all_reduces": txt.count("all-reduce("),
        }
        print(name, json.dumps(out[name]), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
