"""The benchmark harness: one cell, one seed, one run.

Everything that belongs to one configuration, traffic mix, metric or
cell is a file found by its name (``Layout``):

- ``configs/<config>.json``: the architecture as run, its serve sizes;
- ``traffic/<mix>.json``: the mix's parameters; its ``kind`` names the
  generator ``traffic/<kind>.py``;
- ``metrics/<metric>.py``: one reader per metric, ``read(ctx)``;
- ``cost/<attention>.py``: operations and bytes from shapes;
- ``checks/<workload>.json``: the limits that decide ``correct``;
- ``peaks.json``: the chip's published peaks by ``device_kind``.

The system under test is ``repro`` (``src/`` beside this directory),
driven through the functions ``python -m repro.launch.serve`` uses:
``serve_config``, ``make_mesh_from_devices`` and ``ServeEngine`` with
its defaults; the weights are made here from the seed (``weights.py``)
and laid out as the program lays them out (``param_shardings``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import threading
import time
import types
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent

# published config key -> repro ModelConfig field
FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "head_dim": "head_dim", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "attention_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings",
}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Layout:
    """Finds a cell's files by the names in ``BENCHMARK.json``."""

    def __init__(self, root: Path | None = None):
        self.root = Path(root) if root is not None else BENCH.parent
        self.bench = self.root / "bench"
        self.manifest = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = self.bench / "traffic" / f"{name}.json"
        return json.loads(path.read_text())

    def generator(self, kind: str):
        return load_module(self.bench / "traffic" / f"{kind}.py",
                           f"bench_traffic_{kind}")

    def metric(self, name: str):
        return load_module(self.bench / "metrics" / f"{name}.py",
                           "bench_metric_" + name.replace(".", "_"))

    def cost(self, attention: str):
        return load_module(self.bench / "cost" / f"{attention}.py",
                           f"bench_cost_{attention}")

    def check(self, workload: str) -> dict:
        return json.loads(
            (self.bench / "checks" / f"{workload}.json").read_text())

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.bench / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           "bench/peaks.json")
        return table[device_kind]

    def metrics_for(self, workload: str, trace: bool) -> list:
        """The cell's end-to-end metrics (trace off) or per-layer ones."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.manifest[key]
                if "workloads" not in m or workload in m["workloads"]]


# ------------------------------------------------------------- program
def program_config(conf: dict):
    """The served ``ModelConfig``: the architecture's registry entry with
    the file's widths (they are what runs)."""
    from repro.launch.serve import serve_config

    cfg = serve_config(conf["arch"])
    attn = cfg.pattern[0].attn
    if attn != conf["attention"]:
        raise ValueError(f"{conf['arch']}: registry attention {attn!r}, "
                         f"file says {conf['attention']!r}")
    kw = {FIELDS[k]: v for k, v in conf.items() if k in FIELDS}
    dt = conf.get("torch_dtype", "bfloat16")
    return cfg.replace(dtype=dt, param_dtype=dt, **kw)


def make_params(cfg, mesh, seed: int):
    """Weights from the seed, born on the device(s) where they live."""
    import jax.numpy as jnp
    from repro.models.lm import meta_shape_structs, param_meta
    from repro.serve import param_shardings
    from weights import make_tree

    shapes = meta_shape_structs(param_meta(cfg), jnp.dtype(cfg.param_dtype))
    tp = mesh.shape.get("model", 1) > 1
    return make_tree(seed, shapes, cfg.param_dtype,
                     param_shardings(cfg, mesh) if tp else None)


def memory_line(where: str) -> int:
    import jax

    peak = 0
    parts = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        peak = max(peak, s.get("peak_bytes_in_use", 0))
        parts.append(f"dev{d.id} in_use={s.get('bytes_in_use')} "
                     f"peak={s.get('peak_bytes_in_use')} "
                     f"limit={s.get('bytes_limit')}")
    log(f"memory {where}: " + "; ".join(parts))
    return peak


class CompileCounter:
    """Counts backend compilations (and persistent-cache loads)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _n = 0
    _registered = False

    def __init__(self):
        from jax import monitoring

        if not CompileCounter._registered:
            def on(event, duration, **kw):
                if event == CompileCounter.EVENT:
                    CompileCounter._n += 1
            monitoring.register_event_duration_secs_listener(on)
            CompileCounter._registered = True

    @property
    def n(self) -> int:
        return CompileCounter._n


def use_cache_dir() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names another."""
    import os
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(BENCH.parent / ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# -------------------------------------------------------------- driver
class Rec:
    """One request the traffic sent, with the times the benchmark keeps."""

    __slots__ = ("index", "prompt", "max_new", "t_due", "measured", "req",
                 "t_submit", "t_first", "t_done", "out", "error", "late")

    def __init__(self, index, prompt, max_new, t_due, measured, req):
        self.index, self.prompt, self.max_new = index, prompt, max_new
        self.t_due, self.measured, self.req = t_due, measured, req
        self.t_submit = self.t_first = self.t_done = None
        self.out = None
        self.error = None
        self.late = False       # unfinished at the drain limit

    @property
    def done(self) -> bool:
        return self.req.done.is_set()

    def settle(self):
        """Copy the program's stamps and tokens into plain values."""
        r = self.req
        self.t_submit, self.t_first, self.t_done = (r.t_submit, r.t_first,
                                                    r.t_done)
        self.error = None if r.error is None else repr(r.error)
        if r.done.is_set() and r.error is None:
            self.out = np.asarray([int(np.asarray(t).reshape(-1)[0])
                                   for t in r.out_tokens], np.int32)
        self.req = None


class Driver:
    """What a traffic generator drives: submits requests with tokens made
    from the seed, opens and closes the measured window, and reads the
    engine's counters at its edges."""

    def __init__(self, eng, seed: int, vocab: int, seconds: float,
                 barrier, counter: CompileCounter, capture=None):
        self.eng = eng
        self.vocab = vocab
        self.seconds = float(seconds)
        self.words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
        # every request's first token differs from every other's, so no
        # two prompts share a prefix (the prefix cache finds nothing)
        self.first = np.random.default_rng(self.words + [1]).permutation(
            vocab)
        self.recs: list[Rec] = []
        self.barrier = barrier
        self.counter = counter
        self.capture = capture
        self.edges = {}
        self._threads = []

    @staticmethod
    def now() -> float:
        return time.monotonic()

    def tokens(self, index: int, length: int) -> np.ndarray:
        rng = np.random.default_rng(self.words + [2, index])
        t = rng.integers(0, self.vocab, length, dtype=np.int32)
        t[0] = self.first[index % self.vocab]
        return t

    def submit(self, prompt_len: int, max_new: int, t_due: float,
               measured: bool, index: int) -> Rec:
        from repro.serve import Request

        toks = self.tokens(index, prompt_len)
        req = Request(index, toks, max_new_tokens=int(max_new))
        rec = Rec(index, toks, int(max_new), t_due, measured, req)
        self.recs.append(rec)
        self.eng.submit(req)
        return rec

    def counts(self) -> dict:
        """The engine's counters: plain attributes, each read whole."""
        e = self.eng
        return {"ticks": e.stats_ticks, "decode_tokens": e.stats_decode_tokens,
                "occupancy_sum": e.stats_occupancy_sum,
                "prefill_calls": e.stats_prefill_calls}

    def _edge(self, name: str):
        """Counters now; the barrier (an op queued behind every dispatch
        so far) stamps when the device has finished all of them."""
        stats = self.counts()
        stats["t_host"] = self.now()
        stats["compiles"] = self.counter.n
        self.edges[name] = stats

        def wait():
            self.barrier()
            stats["t_device"] = self.now()

        th = threading.Thread(target=wait, daemon=True)
        th.start()
        self._threads.append(th)

    def open_window(self) -> float:
        self._edge("open")
        t0 = self.edges["open"]["t_host"]
        if self.capture is not None:
            span = min(5.0, self.seconds / 2)
            start = t0 + (self.seconds - span) / 2
            th = threading.Thread(target=self._trace, args=(start, span),
                                  daemon=True)
            th.start()
            self._threads.append(th)
        return t0

    def _trace(self, start, span):
        time.sleep(max(0.0, start - self.now()))
        self.capture.start()
        self.trace_counts = [self.counts()]
        time.sleep(span)
        self.trace_counts.append(self.counts())
        self.capture.stop()

    def close_window(self) -> float:
        self._edge("close")
        return self.edges["close"]["t_host"]

    def finish_edges(self):
        for th in self._threads:
            th.join()

    def warm(self, pairs, base: int):
        """Serve one round of ``batch`` prompts of each ``length``, so
        every (length, batch bucket) program the traffic can reach is
        compiled before the window; a round the intake split is sent
        again.  ``base``: first request index of the warm-up range."""
        idx = base
        for length, batch in pairs:
            for _ in range(6):
                calls = self.eng.stats_prefill_calls
                recs = []
                for _ in range(batch):
                    recs.append(self.submit(length, 2, self.now(), False,
                                            idx))
                    idx += 1
                for r in recs:
                    r.req.wait(600)
                if self.eng.stats_prefill_calls - calls == 1:
                    break
            else:
                raise RuntimeError(f"warm-up could not serve {batch} "
                                   f"prompts of {length} in one round")
        self.barrier()
        for r in [r for r in self.recs if r.index >= base]:
            self.recs.remove(r)


def watch(eng, stop: threading.Event, every: float = 5.0):
    """Log the engine's progress every ``every`` seconds until ``stop``."""
    t0 = time.monotonic()
    while not stop.wait(every):
        log(f"progress t={time.monotonic() - t0:.1f}s ticks={eng.stats_ticks} "
            f"decode_tokens={eng.stats_decode_tokens} "
            f"prefills={eng.stats_prefill_calls} "
            f"admission_blocks={eng.stats_admission_blocks} "
            f"free_pages={eng.pager.free_pages if eng.pager else None}")


def host_tasks(events, t0_mono: float):
    """Running spans of UMT tasks from the runtime tracer's events:
    [(start_ns, end_ns, task name)] on the monotonic clock, a task's
    monitored blocks cut out."""
    spans, open_ = [], {}
    for t, kind, wid, _core, info in events:
        t_ns = (t0_mono + t) * 1e9
        if kind == "task_start":
            open_[wid] = [t_ns, info]
        elif kind == "task_end" and wid in open_:
            s, name = open_.pop(wid)
            spans.append((s, t_ns, name))
        elif kind == "block" and wid in open_:
            s, name = open_[wid]
            spans.append((s, t_ns, name))
            open_[wid] = [None, name]
        elif kind == "unblock" and wid in open_:
            open_[wid][0] = t_ns
    return [s for s in spans if s[0] is not None]


def core_busy_share(events, t0_mono, n_cores, window) -> float:
    """Share of core-seconds in ``window`` (monotonic s) in which a core
    had at least one runnable worker (the runtime tracer's own rule)."""
    w0, w1 = window
    count = [0] * n_cores
    since = [w0] * n_cores
    busy = 0.0
    for t, kind, _wid, core, _ in sorted(events, key=lambda e: e[0]):
        t = t0_mono + t
        if kind in ("spawn", "wake", "unblock"):
            d = 1
        elif kind in ("park", "block"):
            d = -1
        else:
            continue
        if not 0 <= core < n_cores:
            continue
        if t > w0 and count[core] >= 1:
            busy += max(0.0, min(t, w1) - max(since[core], w0))
        count[core] += d
        since[core] = max(t, w0)
    for c in range(n_cores):
        if count[c] >= 1:
            busy += max(0.0, w1 - max(since[c], w0))
    return busy / (n_cores * (w1 - w0))


def backlog(recs, t: float) -> int:
    """Requests submitted and not yet completed at monotonic time ``t``."""
    return sum(1 for r in recs if r.t_submit is not None and r.t_submit <= t
               and (r.t_done is None or r.t_done > t))


# ---------------------------------------------------------- correctness
def pick_sample(recs, n: int, seed: int):
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [r for r in recs if r.out is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 3])
    take = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(take)]


def compare(conf: dict, seed: int, sample, control: bool = False) -> dict:
    """Teacher-forced reference over each sampled prompt and its served
    tokens: the widest gap by which a served token's logit lies below
    the reference's best.  With ``control`` the float8 forward stands in
    for the program: at each position its first-ranked token takes the
    served token's place, and ``logit_gap`` is that token's gap (the
    served tokens' own reading is kept as ``served_logit_gap``)."""
    from reference import Reference, gaps

    ref = Reference(conf, seed)
    worst, worst_ctrl, n_tok = 0.0, 0.0, 0
    for r in sample:
        lg = np.asarray(ref.logits(r.prompt, r.out), np.float32)
        worst = max(worst, float(gaps(lg, r.out).max()))
        n_tok += len(r.out)
        if control:
            low = np.asarray(ref.logits(r.prompt, r.out, low=True))
            worst_ctrl = max(worst_ctrl,
                             float(gaps(lg, low.argmax(-1)).max()))
    out = {"logit_gap": worst, "tokens": n_tok, "requests": len(sample)}
    if control:
        out["served_logit_gap"], out["logit_gap"] = worst, worst_ctrl
    return out


# ------------------------------------------------------------------ run
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             layout: Layout | None = None, t_start: float | None = None,
             require_tpu: bool = True, control: bool = False,
             keep_trace: str | None = None,
             mix_overrides: dict | None = None, check: bool = True):
    """One run of one cell.  Returns the result object the command
    prints (``correct``, ``attempted``, ``failed``, ``metrics``,
    ``device``, ``checks`` and, traced, ``breakdown``).  ``mix_overrides``
    and ``check=False`` (no reference) serve the rate sweep."""
    t_start = time.monotonic() if t_start is None else t_start
    # The program's prefix trie walks itself recursively, one frame per
    # cached page (PrefixCache.evict_lru): an 8k-token context at 8
    # tokens a page is deeper than Python's default limit of 1,000.
    sys.setrecursionlimit(max(sys.getrecursionlimit(), 100_000))
    layout = layout or Layout()
    w = layout.workload(workload)
    conf = layout.config(w["config"])
    mix = dict(layout.traffic(w["traffic"]), **(mix_overrides or {}))
    limits = layout.check(workload)

    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < w["chips"]):
        raise NoChip(f"JAX found {len(devs)} {devs[0].platform} device(s); "
                     f"{workload} needs {w['chips']} TPU chip(s)")
    devs = devs[:w["chips"]]
    try:
        peaks = layout.peaks(devs[0].device_kind)
    except KeyError:
        if require_tpu:
            raise
        peaks = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {json.dumps(device)}")
    log(f"compile cache: {use_cache_dir()}")
    counter = CompileCounter()

    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import UMTRuntime
    from repro.launch.mesh import make_mesh_from_devices
    from repro.serve import ServeEngine

    cfg = program_config(conf)
    mesh = make_mesh_from_devices(",".join(str(x) for x in conf["mesh"]))
    params = jax.block_until_ready(make_params(cfg, mesh, seed))
    memory_line("after init")

    sv = conf["serve"]
    rt = UMTRuntime(trace=True) if trace else None
    eng = ServeEngine(cfg, params, slots=sv["slots"],
                      cache_len=sv["cache_len"], mesh=mesh,
                      num_pages=sv.get("num_pages"),
                      max_prefill_batch=sv.get("max_prefill_batch"), rt=rt)
    bx = jax.device_put(np.zeros((), np.float32), NamedSharding(mesh, P()))
    bfn = jax.jit(lambda x: x + 1)

    def barrier():
        bfn(bx).block_until_ready()

    capture = None
    if trace:
        import tempfile
        from devtrace import Capture

        capture = Capture(tempfile.mkdtemp(prefix="bench-trace-"))
    gen = layout.generator(mix["kind"]).Traffic(
        mix, seed, seconds, vocab=conf["vocab_size"], slots=sv["slots"])
    drv = Driver(eng, seed, conf["vocab_size"], seconds, barrier, counter,
                 capture)
    cap = min(sv.get("max_prefill_batch") or sv["slots"], sv["slots"])
    buckets = [1 << i for i in range((cap - 1).bit_length() + 1)]
    eng.start()
    stop = threading.Event()
    threading.Thread(target=watch, args=(eng, stop), daemon=True).start()
    try:
        drv.warm([(n, b) for n in gen.prompt_lengths() for b in buckets],
                 base=conf["vocab_size"] // 2)
        memory_line("after warm-up")
        gen.drive(drv)
        drv.finish_edges()
        late = [r for r in drv.recs if not r.done]
        if late:
            log(f"{len(late)} requests unfinished at the drain limit")
    finally:
        eng.close()
        eng.join()
        stop.set()
    for r in late:
        r.late = True
    peak = memory_line("after the window")
    tasks, busy_share = [], None
    if rt is not None:
        events = list(rt.tracer.events)
        tasks = host_tasks(events, rt.tracer.t0)
        o, c = drv.edges["open"]["t_host"], drv.edges["close"]["t_host"]
        busy_share = core_busy_share(events, rt.tracer.t0, rt.n_cores,
                                     (o, c))
    eng.shutdown()
    if rt is not None:
        rt.shutdown()
    for r in drv.recs:
        r.settle()
    edges, recs = drv.edges, drv.recs
    trace_counts = getattr(drv, "trace_counts", None)
    del eng, params, drv, rt, bx
    gc.collect()

    tr = None
    if capture is not None:
        import shutil
        from glob import glob

        from devtrace import describe

        if keep_trace:
            pb = glob(str(Path(capture.dir) / "**" / "*.xplane.pb"),
                      recursive=True)
            (Path(keep_trace) / f"{workload}.describe.json").write_text(
                json.dumps(describe(pb[0])))
        t0r = time.monotonic()
        tr = capture.reduce(tasks)
        log(f"trace reduced in {time.monotonic() - t0r:.3f} s: window "
            f"{tr.window_s:.6f} s, "
            f"{sum(len(d['ops']) for d in tr.devices.values())} device "
            "op events")
        if keep_trace:
            tr.save(Path(keep_trace) / f"{workload}.trace.json.gz")
        shutil.rmtree(capture.dir, ignore_errors=True)

    # correctness: the reference over a sample of what the window served
    measured = [r for r in recs if r.measured]
    failed = [r for r in measured
              if r.late or r.out is None or len(r.out) != r.max_new]
    sample = (pick_sample(recs, mix.get("check_requests", 2), seed)
              if check else [])
    t0c = time.monotonic()
    cmp = compare(conf, seed, sample, control=control) if sample else {}
    log(f"reference over {cmp.get('requests', 0)} requests, "
        f"{cmp.get('tokens', 0)} served tokens, in "
        f"{time.monotonic() - t0c:.3f} s")
    checks = {}
    gap_limit = limits["logit_gap"]["limit"]
    checks["logit_gap"] = {"value": cmp.get("logit_gap"), "limit": gap_limit}
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    checks["compiles_in_window"] = {
        "value": edges["close"]["compiles"] - edges["open"]["compiles"],
        "limit": 0}
    correct = not failed and (not check or (
        bool(sample) and cmp["logit_gap"] <= gap_limit))

    from reference import Model

    ctx = types.SimpleNamespace(
        workload=workload, config=conf, mix=mix, cell=w, seed=seed,
        seconds=seconds, setup_s=edges["open"]["t_host"] - t_start,
        edges=edges, records=recs, measured=measured, trace=tr,
        trace_counts=trace_counts, host_core_busy=busy_share,
        peaks=peaks, model=Model(conf), chips=len(devs),
        cost=layout.cost(conf["attention"]))
    metrics = {}
    for m in layout.metrics_for(workload, trace):
        val = layout.metric(m["name"]).read(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    if trace:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
    device["memory_peak_bytes"] = int(peak)
    result = {"correct": bool(correct), "attempted": len(measured),
              "failed": len(failed), "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = tr.breakdown()
    if control:
        result["served_logit_gap"] = cmp.get("served_logit_gap")
    result["backlog"] = [backlog(recs, edges[e]["t_host"])
                         for e in ("open", "close")]
    result["checks"] = checks
    return result
