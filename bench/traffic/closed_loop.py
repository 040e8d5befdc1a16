"""Closed loop: a fixed set of clients, each sending its next request the
moment its previous one completes, as an offline batch job does.

Parameters (the mix's JSON): ``clients``; ``prompt_lengths``, client
``k`` keeping the ``k mod len(prompt_lengths)``-th; ``output_tokens``
per request.  Client ``k``'s first request asks for (k + 1) / clients
of ``output_tokens``, so completions are spread over the cycle from the
start instead of coming in waves.  Set-up starts the clients one at a
time, each when the one before has its first token (one prefill in
flight); the window opens once every client has started.  After the
window no request is sent, and the run waits up to ``drain_s`` for those
in flight.  Every request sent counts as attempted.

The seed draws the tokens only.  A deal of lengths and stagger drawn
from it changed which prefills fell inside the window, and with them the
work: seeds differed by up to 15% where one seed repeated within 1%.
"""
from __future__ import annotations

import time


class Traffic:
    def __init__(self, p: dict, seed: int, seconds: float, vocab: int,
                 slots: int):
        self.p = p
        self.seconds = float(seconds)
        n, lengths = p["clients"], p["prompt_lengths"]
        self.length = [int(lengths[k % len(lengths)]) for k in range(n)]

    def prompt_lengths(self) -> list:
        return sorted(set(self.p["prompt_lengths"]))

    def drive(self, d) -> None:
        """Send the mix through the driver ``d``; return once the window
        has closed and what it sent is done, or the drain limit passed."""
        p, n = self.p, self.p["clients"]
        out = p["output_tokens"]
        recs, idx = [None] * n, 0

        def send(c, max_new):
            nonlocal idx
            recs[c] = d.submit(self.length[c], max_new, d.now(), True, idx)
            idx += 1

        started, t_open = 0, None
        setup_limit = d.now() + p.get("setup_limit_s", 900)
        while True:
            if t_open is None:
                ready = (not started
                         or recs[started - 1].req.t_first is not None)
                if ready and started == n:
                    t_open = d.open_window()
                elif ready:
                    send(started, max(2, round(out * (started + 1) / n)))
                    started += 1
                elif d.now() > setup_limit:
                    raise RuntimeError("set-up did not start every client")
            elif d.now() >= t_open + self.seconds:
                break
            for c in range(started):
                if recs[c].done:
                    if recs[c].req.error is not None:
                        raise RuntimeError(f"request {recs[c].index} "
                                           "failed") from recs[c].req.error
                    send(c, out)
            time.sleep(0.001)
        d.close_window()
        limit = d.now() + p["drain_s"]
        while d.now() < limit and not all(r.done for r in recs):
            time.sleep(0.01)
