"""The reduction from device trace to metrics, on hand-made intervals
with known answers and on a trace recorded on a TPU v5e chip."""
import json
from pathlib import Path

import pytest

from devtrace import Trace, _intersect, _union
from harness import core_busy_share, host_tasks

FIXTURES = Path(__file__).resolve().parents[1] / "fixtures"


def hand_trace():
    ops = [[0, 10, "fusion.1"], [5, 10, "fusion.2"],
           [30, 10, "all-reduce.1"], [35, 10, "fusion.3"],
           [90, 20, "fusion.4"]]
    mods = [[0, 20, "jit_decode_step(3)"], [30, 20, "jit_decode_step(3)"],
            [85, 30, "jit_prefill_step(7)"]]
    tasks = [[10, 50, "serve.decode"], [40, 95, "serve.prefill:12x2"]]
    return Trace([0, 100], 0, {"0": {"ops": ops, "modules": mods}}, tasks)


def test_intervals():
    assert _union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert _intersect([[0, 3], [5, 8]], [[2, 6]]) == [[2, 3], [5, 6]]


def test_busy_idle_modules_collectives_gaps():
    tr = hand_trace()
    # busy: [0, 15] + [30, 45] + [90, 100 (clipped)]
    assert tr.busy("0") == [[0, 15], [30, 45], [90, 100]]
    assert tr.busy_s() == pytest.approx(40e-9)
    assert tr.idle_share() == pytest.approx(0.6)
    # the prefill ends past the window: not counted
    assert tr.module_time("0", {"jit_decode_step"}) == (
        pytest.approx(40e-9), 2)
    assert tr.module_time("0", {"jit_prefill_step"}) == (0.0, 0)
    # all-reduce [30, 40] overlaps fusion.3 from 35: 5 ns exposed
    assert tr.exposed_collective_s("0") == pytest.approx(5e-9)
    assert tr.gaps("0") == [[15, 30], [45, 90]]
    assert tr.label(15, 30) == "serve.decode"
    assert tr.label(45, 90) == "serve.prefill+serve.decode"
    b = tr.breakdown()
    assert b["idle_gaps"][0] == ["serve.prefill+serve.decode",
                                 pytest.approx(45e-9)]
    assert [n for n, _ in b["device_ops"]][:2] == ["fusion.1", "fusion.2"]


def test_json_round_trip(tmp_path):
    tr = hand_trace()
    tr.save(tmp_path / "t.json.gz")
    back = Trace.load(tmp_path / "t.json.gz")
    assert back.to_json() == json.loads(json.dumps(tr.to_json()))


def test_host_tasks_cut_out_blocks():
    events = [(0.0, "task_start", 1, 0, "serve.decode"),
              (1.0, "block", 1, 0, None), (3.0, "unblock", 1, 0, None),
              (4.0, "task_end", 1, 0, "serve.decode")]
    spans = host_tasks(events, 10.0)
    assert spans == [(10e9, 11e9, "serve.decode"),
                     (13e9, 14e9, "serve.decode")]


def test_core_busy_share():
    # core 0 runnable from 1 to 3 and 5 to 9 of a 10 s window, core 1 never
    events = [(1.0, "spawn", 1, 0, None), (3.0, "park", 1, 0, None),
              (5.0, "wake", 1, 0, None)]
    assert core_busy_share(events, 0.0, 2, (0.0, 10.0)) == \
        pytest.approx((2 + 5) / 20)


def test_recorded_chip_trace():
    """A window of a traced run of ``minicpm3-4b.longctx-decode`` on one
    v5e chip, reduced when it was recorded; ``expected.json`` holds what
    the reduction gave then."""
    tr = Trace.load(FIXTURES / "longctx-decode.trace.json.gz")
    want = json.loads((FIXTURES / "longctx-decode.expected.json").read_text())
    assert tr.window_s == pytest.approx(want["window_s"])
    assert tr.busy_s() == pytest.approx(want["busy_s"])
    assert tr.idle_share() == pytest.approx(want["idle_share"])
    t, n = tr.module_time_mean({"jit_decode_step"})
    assert (t, n) == (pytest.approx(want["decode_s"]), want["decode_n"])
    assert n >= 3
    assert tr.exposed_collective_s("0") == pytest.approx(
        want["exposed_collective_s"])
    assert 0 < tr.busy_s() <= tr.window_s
    assert t <= tr.busy_s() + 1e-9
    b = tr.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert [g[0] for g in b["idle_gaps"]] == want["gap_labels"]
