"""Tracer spans (host-only, fast): nesting gives each span its parent on
its own thread, spans carry the request ids they serve, self time leaves
out the children, and a disabled tracer hands back one shared no-op and
records nothing."""
import threading

import pytest

from repro.core import Tracer
from repro.core.tracing import NO_SPAN


def test_spans_nest_with_parent_and_rids():
    tr = Tracer(enabled=True)
    with tr.span("tick"):
        with tr.span("tick.finish", (7,)):
            pass
        with tr.span("tick.flush"):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"tick", "tick.finish", "tick.flush"}
    tick = spans["tick"]
    assert tick.parent is None and tick.rids == ()
    assert spans["tick.finish"].parent == tick.span_id
    assert spans["tick.flush"].parent == tick.span_id
    assert spans["tick.finish"].rids == (7,)
    for s in spans.values():
        assert tick.start <= s.start <= s.end <= tick.end
        assert s.thread == threading.get_ident()
    # spans share the event list and clock of the runtime's events
    assert sum(1 for e in tr.events if e[1] == "span") == 3


def test_span_parents_are_per_thread():
    tr = Tracer(enabled=True)
    inside, release = threading.Event(), threading.Event()

    def other():
        with tr.span("prefill.dispatch", (1, 2)):
            inside.set()
            release.wait(5)

    th = threading.Thread(target=other)
    with tr.span("tick"):
        th.start()
        inside.wait(5)
        release.set()
        th.join(5)
    spans = {s.name: s for s in tr.spans()}
    # open at the same time, but on another thread: not a child
    assert spans["prefill.dispatch"].parent is None
    assert spans["prefill.dispatch"].thread != spans["tick"].thread
    assert spans["prefill.dispatch"].rids == (1, 2)


def test_span_self_time_leaves_out_children():
    tr = Tracer(enabled=True)
    tr.events += [
        (0.0, "span", 1, -1, (10.0, "tick", 1, None, ())),
        (2.0, "span", 1, -1, (5.0, "tick.dispatch", 2, 1, ())),
        (4.0, "span", 1, -1, (6.0, "tick.finish", 3, 1, (9,))),
        (8.0, "span", 1, -1, (9.0, "tick.finish", 4, 1, (8,))),
        (20.0, "span", 1, -1, (21.0, "tick", 5, None, ())),
        (3.0, "block", 0, 0, None),        # runtime events are ignored
    ]
    got = tr.span_self_s()
    # tick: 10 - union([2,5], [4,6], [8,9]) = 10 - 5, plus 1
    assert got == pytest.approx({"tick": 6.0, "tick.dispatch": 3.0,
                                 "tick.finish": 3.0})
    assert [s.name for s in tr.spans()][:2] == ["tick", "tick.dispatch"]


def test_disabled_tracer_spans_are_one_shared_noop():
    tr = Tracer(enabled=False)
    a, b = tr.span("tick"), tr.span("insert", (3,))
    assert a is b is NO_SPAN
    with a:
        with b:
            pass
    assert tr.events == [] and tr.spans() == [] and tr.span_self_s() == {}


def test_concurrent_spans_keep_ids_parents_and_records():
    """More threads than cores opening nested spans at a short switch
    interval: every span is recorded once, ids are unique, and each
    child's parent is a span of its own thread."""
    import sys

    tr = Tracer(enabled=True)
    n_threads, n_spans = 16, 200

    def work(k):
        for i in range(n_spans):
            with tr.span("tick"):
                with tr.span("tick.finish", (k, i)):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ths = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(60)
        assert not any(th.is_alive() for th in ths)
    finally:
        sys.setswitchinterval(old)
    spans = tr.spans()
    assert len(spans) == 2 * n_threads * n_spans
    byid = {s.span_id: s for s in spans}
    assert len(byid) == len(spans)
    for s in spans:
        if s.name == "tick":
            assert s.parent is None
        else:
            parent = byid[s.parent]
            assert parent.name == "tick" and parent.thread == s.thread
            assert parent.start <= s.start <= s.end <= parent.end
