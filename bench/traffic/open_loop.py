"""Open loop: independent users sending on a schedule, whether or not
earlier requests have finished.

Parameters (the mix's JSON): ``rate`` (requests/s); ``ramp_s`` of
traffic before the window, so its queue is in steady state when the
window opens; ``prompt`` and ``output``, lognormal lengths given by
``median``, ``sigma``, ``min``, ``max`` and, for prompts, ``round_up_to``
(a stated set of lengths); ``drain_s``, how long after the window the
traffic keeps sending and waits for the requests due in the window.

Each segment (ramp, window, tail) holds round(rate x length) arrivals.
Its gaps are the exponential distribution's quantiles at (i + 0.5)/n,
scaled to fill the segment, and its lengths the lognormal's, each dealt
out in one fixed shuffled order.  The seed draws the tokens only: the
order decides where bursts fall and so the tail, and a closed loop whose
deal came from the seed moved by up to 15% from seed to seed.
"""
from __future__ import annotations

import math
import time
from statistics import NormalDist

import numpy as np


def _lognormal(q: dict, u):
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    v = np.clip(q["median"] * np.exp(q["sigma"] * z), q["min"], q["max"])
    if "round_up_to" in q:
        b = np.asarray(sorted(q["round_up_to"]))
        return b[np.searchsorted(b, v)].astype(int)
    return np.ceil(v).astype(int)


class Traffic:
    def __init__(self, p: dict, seed: int, seconds: float, vocab: int,
                 slots: int):
        self.p = p
        self.seconds = float(seconds)
        rng = np.random.default_rng(13)
        # (offset from the ramp's start, prompt, output, measured)
        self.plan = []
        start = 0.0
        for seg, length in (("ramp", p["ramp_s"]), ("window", seconds),
                            ("tail", p["drain_s"])):
            n = max(1, round(p["rate"] * length))
            u = (np.arange(n) + 0.5) / n
            gaps = -np.log1p(-u)
            gaps = rng.permutation(gaps) * (length / gaps.sum())
            at = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            prompt = rng.permutation(_lognormal(p["prompt"], u))
            output = rng.permutation(_lognormal(p["output"], u))
            for t, pl, ol in zip(at, prompt, output):
                self.plan.append((float(t), int(pl), max(2, int(ol)),
                                  seg == "window"))
            start += length

    def prompt_lengths(self) -> list:
        return sorted(set(self.p["prompt"]["round_up_to"]))

    def drive(self, d) -> None:
        """Send the mix through the driver ``d``; return once the window
        has closed and what it sent is done, or the drain limit passed."""
        p = self.p
        base = d.now() + 0.05
        t_open = base + p["ramp_s"]
        t_close = t_open + self.seconds
        opened = closed = False
        measured = []
        sent = []
        for i, (off, plen, out, meas) in enumerate(self.plan):
            due = base + off
            while True:
                now = d.now()
                if not opened and now >= t_open:
                    d.open_window()
                    opened = True
                if not closed and now >= t_close:
                    d.close_window()
                    closed = True
                nxt = min(due, t_open if not opened else math.inf,
                          t_close if not closed else math.inf)
                if now >= due:
                    break
                time.sleep(min(nxt - now, 0.05))
            if closed and measured and all(r.done for r in measured):
                break
            rec = d.submit(plen, out, due, meas, i)
            sent.append(rec)
            if meas:
                measured.append(rec)
        if not closed:
            time.sleep(max(0.0, t_close - d.now()))
            d.close_window()
        limit = t_close + p["drain_s"]
        while d.now() < limit and not all(r.done for r in sent):
            time.sleep(0.01)
