"""UMT core: event-channel algebra, task graph, runtime behaviour."""
import threading
import time

import pytest
from hypothesis_stub import given, settings, st

from repro.core import EventChannel, Task, UMTRuntime, io
from repro.core.eventchannel import umt_enable
from repro.core.task import (AtomicCounter, DependencyTracker, ReadyQueue,
                             ShardedReadyQueue)


# ------------------------------------------------------------ event channel
def test_eventchannel_packing_roundtrip():
    ch = EventChannel(0)
    try:
        for _ in range(3):
            ch.write_block()
        for _ in range(5):
            ch.write_unblock()
        b, u = ch.read()
        assert (b, u) == (3, 5)
        assert ch.read() == (0, 0)          # read drains
    finally:
        ch.close()


@given(st.lists(st.sampled_from(["b", "u"]), max_size=200))
@settings(max_examples=50, deadline=None)
def test_eventchannel_counts_any_interleaving(ops):
    ch = EventChannel(0)
    try:
        for o in ops:
            (ch.write_block if o == "b" else ch.write_unblock)()
        b, u = ch.read()
        assert b == ops.count("b")
        assert u == ops.count("u")
    finally:
        ch.close()


def test_eventchannel_concurrent_writers_never_lose_events():
    ch = EventChannel(0)
    n, per = 8, 500

    def w():
        for _ in range(per):
            ch.write_block()
            ch.write_unblock()

    ts = [threading.Thread(target=w) for _ in range(n)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    b, u = ch.read()
    assert b == u == n * per
    ch.close()


def test_umt_enable_one_channel_per_core():
    chans = umt_enable(7)
    assert [c.core for c in chans] == list(range(7))
    fds = {c.fd for c in chans}
    assert len(fds) == 7
    [c.close() for c in chans]


# ------------------------------------------------------------- dependencies
def _mk(fn=lambda: None, in_=(), out=()):
    return Task(fn, (), {}, in_, out, None, None)


def test_dep_reader_after_writer():
    d = DependencyTracker()
    w = _mk(out=("x",))
    assert d.register(w) == 0
    r = _mk(in_=("x",))
    assert d.register(r) == 1
    assert r in w.succs


def test_dep_writer_after_readers_war():
    d = DependencyTracker()
    w1 = _mk(out=("x",))
    d.register(w1)
    r1, r2 = _mk(in_=("x",)), _mk(in_=("x",))
    d.register(r1)
    d.register(r2)
    w2 = _mk(out=("x",))
    n = d.register(w2)
    assert n == 3  # w1 (WAW) + two readers (WAR)


def test_dep_done_predecessors_do_not_block():
    d = DependencyTracker()
    w = _mk(out=("x",))
    d.register(w)
    w.done_ev.set()
    r = _mk(in_=("x",))
    assert d.register(r) == 0


@given(st.lists(st.tuples(st.booleans(), st.integers(0, 3)), min_size=1,
                max_size=24))
@settings(max_examples=50, deadline=None)
def test_dep_graph_is_acyclic_and_serialises_writes(spec):
    """Chain of read/write tasks over 4 keys: registration order must
    topologically order all writers of the same key."""
    d = DependencyTracker()
    tasks = []
    for is_write, key in spec:
        t = _mk(out=(key,)) if is_write else _mk(in_=(key,))
        d.register(t)
        tasks.append((t, is_write, key))
    # successors must always have a larger tid (registration order) —
    # i.e. the graph is acyclic by construction
    for t, _, _ in tasks:
        for s in t.succs:
            assert s.tid > t.tid


# ------------------------------------------- sharded ready queue (fast path)
def test_atomic_counter_concurrent_adds():
    c = AtomicCounter()
    n, per = 8, 2000

    def bump():
        for _ in range(per):
            c.add(1)

    ts = [threading.Thread(target=bump) for _ in range(n)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == n * per


def test_sharded_queue_fifo_per_shard():
    q = ShardedReadyQueue(3)
    for shard in range(3):
        for i in range(5):
            q.push(_mk(), shard)
    for shard in range(3):
        tids = [q.pop_local(shard).tid for _ in range(5)]
        assert tids == sorted(tids)          # per-shard FIFO preserved
        assert q.pop_local(shard) is None


def test_sharded_queue_steals_only_when_local_empty():
    q = ShardedReadyQueue(2)
    local, remote = _mk(), _mk()
    q.push(remote, 1)
    q.push(local, 0)
    # local work present: dispatch (pop_local then steal) takes it, no steal
    assert q.pop_local(0) is local
    assert q.steals.value == 0
    # local dry: dispatch falls through to steal of the remote task
    assert q.pop_local(0) is None
    t, victim = q.steal(0)
    assert t is remote and victim == 1
    assert q.steals.value == 1
    assert len(q) == 0


def test_sharded_queue_steal_takes_oldest():
    q = ShardedReadyQueue(2)
    first, second = _mk(), _mk()
    q.push(first, 1)
    q.push(second, 1)
    t, victim = q.steal(0)
    assert t is first and victim == 1        # head steal: victim FIFO intact
    assert q.pop_local(1) is second
    assert q.steal_batches.value == 0        # 2 < steal_half_min: single


def test_sharded_queue_steal_half_when_imbalanced():
    """A dry thief facing a victim holding >= steal_half_min tasks takes
    half the victim's deque in one steal: oldest returned, the next
    half-minus-one re-homed onto the thief's shard, FIFO order preserved
    on both sides, batch counters ticked."""
    q = ShardedReadyQueue(3)
    ts = [_mk() for _ in range(8)]
    for t in ts:
        q.push(t, 1)
    t, victim = q.steal(0)
    assert t is ts[0] and victim == 1        # nearest neighbour, oldest
    assert q.steal_batches.value == 1
    assert q.steal_batch_tasks.value == 3    # half of 8, minus the claim
    # thief's local shard now serves the moved tasks in their old order
    assert [q.pop_local(0) for _ in range(3)] == ts[1:4]
    assert q.pop_local(0) is None
    # victim keeps the newest half, FIFO intact
    assert [q.pop_local(1) for _ in range(4)] == ts[4:8]
    assert q.pop_local(1) is None
    assert len(q) == 0


def test_sharded_queue_steal_below_threshold_takes_one():
    q = ShardedReadyQueue(2, steal_half_min=4)
    ts = [_mk() for _ in range(3)]
    for t in ts:
        q.push(t, 1)
    t, _ = q.steal(0)
    assert t is ts[0]
    assert q.steal_batches.value == 0 and q.steal_batch_tasks.value == 0
    assert q.pop_local(0) is None            # nothing re-homed
    assert [q.pop_local(1) for _ in range(2)] == ts[1:]


def test_sharded_queue_topology_steal_order():
    """Synthetic 2-socket topology (ROADMAP open item): shards 0-1 on
    socket A, 2-3 on socket B, inter-socket distance 10x intra.  The
    steal walk must exhaust the local socket before crossing it, with
    distance ties broken by the old ring order."""
    topo = [[0, 1, 10, 10],
            [1, 0, 10, 10],
            [10, 10, 0, 1],
            [10, 10, 1, 0]]
    q = ShardedReadyQueue(4, topology=topo)
    assert q._steal_order[0] == (1, 2, 3)
    assert q._steal_order[1] == (0, 2, 3)    # sibling first, then ring
    assert q._steal_order[2] == (3, 0, 1)
    assert q._steal_order[3] == (2, 0, 1)
    # functionally: a dry thief prefers the same-socket victim even when
    # the ring walk would reach the remote socket first
    far, near = _mk(), _mk()
    q.push(far, 2)                           # ring-nearest to shard 1
    q.push(near, 0)                          # socket sibling of shard 1
    t, victim = q.steal(1)
    assert t is near and victim == 0
    t, victim = q.steal(1)
    assert t is far and victim == 2


def test_sharded_queue_default_walk_is_ring_order():
    """topology=None (and any all-ties topology) keeps the pre-topology
    nearest-index walk bit-for-bit."""
    q = ShardedReadyQueue(4)
    assert q._steal_order[2] == (3, 0, 1)
    uniform = ShardedReadyQueue(4, topology=[[1] * 4] * 4)
    assert uniform._steal_order == q._steal_order


def test_runtime_accepts_topology_matrix():
    from repro.core import UMTRuntime

    with UMTRuntime(n_cores=2, umt=True, trace=False,
                    topology=[[0, 3], [3, 0]]) as rt:
        assert rt.submit(lambda: 41 + 1).wait() == 42
        assert rt.ready._steal_order == ((1,), (0,))


def test_runtime_stats_surface_steal_batch_counters():
    from repro.core import UMTRuntime

    with UMTRuntime(n_cores=2, umt=True, trace=False) as rt:
        rt.wait_all()
        s = rt.stats()
    assert s["steal_batches"] == rt.ready.steal_batches.value
    assert s["steal_batch_tasks"] == rt.ready.steal_batch_tasks.value


def test_sharded_queue_approx_len_lock_free():
    q = ShardedReadyQueue(4)
    tasks = [_mk() for _ in range(12)]
    for i, t in enumerate(tasks):
        q.push(t, i % 4)
    assert len(q) == 12
    for i in range(12):
        assert q.pop_local(i % 4) is not None
    assert len(q) == 0


def test_push_ready_wakes_at_most_one_worker():
    with UMTRuntime(n_cores=4, umt=True) as rt:
        rt.wait_all()
        time.sleep(0.1)                     # let all workers park
        wakes = []
        orig = rt._wake_for_work
        main = threading.get_ident()

        def counting_wake(core=None):
            # count only push-path wakes (synchronous on this thread) —
            # a Leader rescan racing the submit runs on its own thread
            if threading.get_ident() == main:
                wakes.append(core)
            return orig(core)

        rt._wake_for_work = counting_wake
        h = rt.submit(lambda: None)
        rt._wake_for_work = orig
        h.wait()
        rt.wait_all()
    assert len(wakes) <= 1, wakes


def test_worker_fanout_wakes_parked_worker_promptly():
    """A child pushed to the busy parent's own shard must hand work to a
    parked worker (which steals it) instead of waiting for the Leader's
    backed-off rescan — parked workers can't steal on their own."""
    done = threading.Event()

    # slow rescan: if the push path doesn't wake anyone, the child can't
    # run inside the 0.1 s window below
    with UMTRuntime(n_cores=2, umt=True, scan_interval=0.2) as rt:
        def parent():
            rt.submit(done.set)
            # unmonitored wait: parent stays "runnable" on its core, so
            # the child's home shard looks busy the whole time
            assert done.wait(0.1), \
                "child did not run while parent occupied its core"

        rt.submit(parent).wait()
        rt.wait_all()


def test_completion_fanout_wakes_parked_workers():
    """When one task's completion readies N successors, the completing
    worker pops one — the other N-1 must be handed to parked workers,
    not strand in its shard until the Leader's backed-off rescan."""
    with UMTRuntime(n_cores=4, umt=True, scan_interval=0.2) as rt:
        t0 = time.monotonic()
        rt.submit(lambda: None, out=("x",))
        hs = [rt.submit(lambda: time.sleep(0.05), in_=("x",))
              for _ in range(4)]
        [h.wait() for h in hs]
        dt = time.monotonic() - t0
    # serial on one worker = 4 x 0.05 = 0.2 s; overlapped well under it
    assert dt < 0.15, dt


def test_umt_baseline_equivalence_under_stealing():
    """Same mixed task graph -> same per-key results in all scheduler
    modes (stealing must not break dependency ordering)."""
    def run(umt, sched):
        acc = {}
        lock = threading.Lock()

        def bump(key, i):
            with lock:
                acc[key] = acc.get(key, 0) * 2 + i

        with UMTRuntime(n_cores=4, umt=umt, sched=sched) as rt:
            for i in range(30):
                key = i % 3
                rt.submit(bump, key, i, in_=((key,),), out=((key,),))
            rt.wait_all()
        return acc

    want = run(False, "global")
    assert run(True, "sharded") == want
    assert run(False, "sharded") == want
    assert run(True, "global") == want


def test_sharded_steals_are_traced():
    with UMTRuntime(n_cores=4, umt=True) as rt:
        for i in range(60):
            rt.submit(lambda: time.sleep(0.001))
        rt.wait_all()
        s = rt.stats()
    assert s["sched"] == "sharded"
    assert s["steals"] == rt.ready.steals.value
    traced = sum(1 for e in rt.tracer.events if e[1] == "steal")
    assert traced == s["traced_steals"]


# ------------------------------------------------- Leader batching/ratelimit
def test_leader_coalesces_drains_and_rate_limits_scans():
    """A burst of fine-grained monitored tasks: the Leader coalesces all
    ready eventfds per wakeup (drains happen, counted) and runs at most
    ~one leader_scan per scan_min_gap instead of one per wakeup."""
    t0 = time.monotonic()
    with UMTRuntime(n_cores=2, umt=True) as rt:
        for _ in range(100):
            rt.submit(lambda: io.sleep(0.0005))
        rt.wait_all()
        dt = time.monotonic() - t0
        s = rt.stats()
    assert s["leader_wakeups"] >= 1
    assert s["leader_drains"] >= 1
    assert s["leader_scans"] <= dt / rt.scan_min_gap + 16, s


def test_leader_scan_rate_limit_disabled_still_schedules():
    """scan_min_gap=0 restores scan-per-wakeup; everything still runs."""
    with UMTRuntime(n_cores=2, umt=True, scan_min_gap=0.0) as rt:
        hs = [rt.submit(lambda: io.sleep(0.002)) for _ in range(20)]
        [h.wait() for h in hs]
        rt.wait_all()
        s = rt.stats()
    assert s["leader_scans"] >= 1


def test_leader_scan_min_gap_zero_scans_every_wakeup():
    """The scan_min_gap=0 edge, asserted: with the rate limit off, every
    Leader wakeup (and every timeout poll) passes the since >= 0 gate, so
    leader_scans can never fall below leader_wakeups — except the final
    shutdown wakeup, which breaks out before the scan."""
    with UMTRuntime(n_cores=2, umt=True, scan_min_gap=0.0) as rt:
        hs = [rt.submit(lambda: io.sleep(0.001)) for _ in range(50)]
        [h.wait() for h in hs]
        rt.wait_all()
        s = rt.stats()
    assert s["leader_wakeups"] >= 1
    assert s["leader_scans"] >= s["leader_wakeups"] - 1, s


def test_leader_drains_bounded_by_wakeups():
    """The batched-drain loop runs at most 4 coalescing rounds per wakeup
    and each round drains each core at most once, so leader_drains is
    bounded by 4 * n_cores per wakeup — the stat can prove drains are
    coalesced, not per-event."""
    n_cores = 2
    with UMTRuntime(n_cores=n_cores, umt=True) as rt:
        hs = [rt.submit(lambda: io.sleep(0.001)) for _ in range(100)]
        [h.wait() for h in hs]
        rt.wait_all()
        s = rt.stats()
    assert s["leader_drains"] >= 1
    assert s["leader_drains"] <= 4 * n_cores * s["leader_wakeups"], s


def test_leader_scan_min_gap_large_scans_at_most_twice():
    """A huge scan_min_gap collapses scanning to the initial pass (the
    first wakeup always scans — last_scan starts at 0): the rate limiter
    is a hard gate, not advisory."""
    with UMTRuntime(n_cores=2, umt=True, scan_min_gap=100.0) as rt:
        hs = [rt.submit(lambda: io.sleep(0.001)) for _ in range(30)]
        [h.wait() for h in hs]
        rt.wait_all()
        s = rt.stats()
    assert s["leader_scans"] <= 2, s


def test_leader_stats_stay_zero_on_baseline():
    """umt=False never starts the Leader: its stats must stay zero (the
    A/B legs in benchmarks would otherwise be misattributed)."""
    with UMTRuntime(n_cores=2, umt=False) as rt:
        hs = [rt.submit(lambda: io.sleep(0.001)) for _ in range(10)]
        [h.wait() for h in hs]
        rt.wait_all()
        s = rt.stats()
    assert s["leader_wakeups"] == 0
    assert s["leader_drains"] == 0
    assert s["leader_scans"] == 0


# ------------------------------------------------------------ runtime basic
def test_runtime_runs_tasks_and_results():
    with UMTRuntime(n_cores=2) as rt:
        hs = [rt.submit(lambda i=i: i * i, name=f"t{i}") for i in range(20)]
        assert [h.wait() for h in hs] == [i * i for i in range(20)]


def test_shutdown_while_leader_spawns_a_worker():
    """The Leader grows the worker set (append, then start) while a
    shutdown may be joining it: shutdown must stop the Leader first, so
    it never joins a worker that was appended but not yet started."""
    from repro.core.runtime import Worker

    rt = UMTRuntime(n_cores=1, umt=True, trace=False)
    appended = threading.Event()

    def slow_spawn_scan():              # runs on the Leader thread
        if appended.is_set():
            return
        w = Worker(rt, 0)
        rt._workers.append(w)
        appended.set()
        time.sleep(0.3)                 # shutdown starts joining here
        w.start()

    rt.leader_scan = slow_spawn_scan
    assert appended.wait(5)
    rt.shutdown()
    assert not rt.leader.is_alive()
    assert not any(w.is_alive() for w in rt._workers)


def test_runtime_dependency_order():
    order = []
    lock = threading.Lock()

    def log(tag):
        with lock:
            order.append(tag)

    with UMTRuntime(n_cores=4) as rt:
        rt.submit(lambda: log("a"), out=("x",))
        rt.submit(lambda: log("b"), in_=("x",), out=("y",))
        rt.submit(lambda: log("c"), in_=("y",))
        rt.wait_all()
    assert order == ["a", "b", "c"]


def test_runtime_exception_propagates():
    def boom():
        raise ValueError("boom")

    with UMTRuntime(n_cores=2) as rt:
        h = rt.submit(boom)
        with pytest.raises(ValueError, match="boom"):
            h.wait()


def test_nested_tasks_and_taskwait():
    results = []

    with UMTRuntime(n_cores=2) as rt:
        def parent():
            hs = [rt.submit(lambda i=i: results.append(i)) for i in range(5)]
            rt.taskwait()           # children done before parent continues
            results.append("after")

        rt.submit(parent).wait()
    assert set(results[:5]) == set(range(5))
    assert results[5] == "after"


def test_baseline_mode_runs_everything_too():
    with UMTRuntime(n_cores=2, umt=False) as rt:
        hs = [rt.submit(lambda i=i: i + 1) for i in range(10)]
        assert [h.wait() for h in hs] == list(range(1, 11))
    assert rt.stats()["umt"] is False


# ----------------------------------------------------- UMT-specific effects
def test_umt_overlaps_blocking_io():
    """4 tasks x 0.15s sleep on ONE core: baseline must serialise
    (>=0.6s); UMT must overlap them (well under 0.4s)."""
    def job():
        io.sleep(0.15)

    t0 = time.monotonic()
    with UMTRuntime(n_cores=1, umt=False) as rt:
        for _ in range(4):
            rt.submit(job)
        rt.wait_all()
    base = time.monotonic() - t0

    t0 = time.monotonic()
    with UMTRuntime(n_cores=1, umt=True) as rt:
        for _ in range(4):
            rt.submit(job)
        rt.wait_all()
    umt = time.monotonic() - t0

    assert base >= 0.55, base
    assert umt <= 0.40, umt


def test_umt_wakes_workers_on_blocked_core():
    """While one task blocks, another must get CPU on the same core."""
    ran = threading.Event()

    def blocker():
        io.sleep(0.3)

    def quick():
        ran.set()

    with UMTRuntime(n_cores=1, umt=True) as rt:
        rt.submit(blocker)
        time.sleep(0.05)            # let blocker start blocking
        rt.submit(quick)
        assert ran.wait(0.2), "task did not run while core was blocked"
        rt.wait_all()
    s = rt.stats()
    assert s["wakes"] >= 1


def test_oversubscription_self_surrender():
    """A herd of workers waking on one core must self-surrender at the
    next scheduling point (paper Fig. 1, T4-T6)."""
    n = 5
    barrier = threading.Barrier(n)

    def job():
        io.call(barrier.wait)    # all block together -> leader spawns help
        time.sleep(0.05)         # unmonitored "compute": herd overlaps ->
        return True              # oversubscription observed at finish

    with UMTRuntime(n_cores=1, umt=True) as rt:
        hs = [rt.submit(job) for _ in range(n)]
        assert all(h.wait() for h in hs)
        rt.wait_all()
        time.sleep(0.05)
        s = rt.stats()
    assert s["spawned"] >= n     # leader actually grew the worker set
    assert s["surrenders"] >= 2  # the herd shed extras at finish points


def test_surrender_hysteresis_defers_parking():
    """With a hysteresis window larger than the run ever reaches, an
    oversubscribed worker never self-surrenders — the observation is
    counted as a deferral instead — and the task graph still drains
    (hysteresis trades churn, never progress).  The default (1) is the
    paper's eager rule, covered by the surrender test above."""
    n = 5
    barrier = threading.Barrier(n)

    def job():
        io.call(barrier.wait)    # all block together -> leader spawns help
        time.sleep(0.05)         # herd overlaps -> oversubscription
        return True

    with UMTRuntime(n_cores=1, umt=True,
                    surrender_hysteresis=10 ** 6) as rt:
        hs = [rt.submit(job) for _ in range(n)]
        assert all(h.wait() for h in hs)
        rt.wait_all()
        time.sleep(0.05)
        s = rt.stats()
    assert s["surrenders"] == 0
    assert s["surrender_deferrals"] > 0


def test_ready_count_converges_when_quiescent():
    with UMTRuntime(n_cores=2, umt=True) as rt:
        for i in range(10):
            rt.submit(lambda: io.sleep(0.02))
        rt.wait_all()
        time.sleep(0.1)
        for c in range(rt.n_cores):
            rt.drain_core(c)
        # Σ ready == number of workers not parked in the pool
        with rt._pool_lock:
            parked = len(rt._pool)
        runnable = len(rt._workers) - parked
        assert sum(rt.ready_count) == runnable, (
            rt.ready_count, runnable, len(rt._workers), parked)


def test_migration_compensation_algebra():
    """Paper §III-B: a *runnable* worker migrated from core A to B must
    move one ready unit from A to B via the missed (block@A, unblock@B)."""
    release = threading.Event()
    started = threading.Event()

    def busy():
        started.set()
        release.wait()          # unmonitored: worker counts as runnable

    with UMTRuntime(n_cores=2, umt=True, scan_interval=0.5) as rt:
        try:
            rt.submit(busy)
            assert started.wait(1)
            time.sleep(0.05)
            for c in (0, 1):
                rt.drain_core(c)
            before = list(rt.ready_count)
            w = next(x for x in rt._workers if x.current_task is not None)
            old = w.core
            new = 1 - old
            w.migrate(new)
            for c in (0, 1):
                rt.drain_core(c)
            after = list(rt.ready_count)
            assert after[old] == before[old] - 1, (before, after, old)
            assert after[new] == before[new] + 1, (before, after, old)
        finally:
            release.set()       # hang-proof: shutdown() waits for `busy`
        rt.wait_all()
