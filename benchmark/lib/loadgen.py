"""Load generation for the serve cells, and the latency arithmetic.

After ``tpu_ddp/serve/loadgen.py`` (``run_load``, ``poisson_arrivals``),
with its three faults corrected: a request's time to first token runs
from when it was DUE, not from when the loop got round to submitting it;
the load runs for a window and not until a finite list is done; and the
request set is a fixed list from the traffic file, which the seed only
orders, so two runs serve the same work.

One thread does everything: submit what is due, step the engine, look at
the clock. ``engine.step()`` blocks for a whole decode step, so arrivals
are seen between steps only; how late the generator ran is reported.
"""

from __future__ import annotations

import time

import numpy as np


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def order_requests(pairs, rng):
    """Endless (prompt_len, output_len) pairs: the fixed list ``pairs``
    in an order drawn from ``rng``, gone through again in a new order
    for as long as asked. Any ``len(pairs)`` requests in a row that
    start at a multiple of it are exactly the list."""
    while True:
        for i in rng.permutation(len(pairs)):
            yield tuple(pairs[i])


def poisson_arrivals(rate: float, rng):
    """Endless arrival offsets in seconds at ``rate`` requests a second."""
    t = 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        yield t


class Load:
    """Drives one engine. ``submit(prompt_len, output_len) -> handle``
    and ``step() -> bool`` are the engine as the runner wraps it; the
    handle has ``token_times`` and ``done`` (``tpu_ddp.serve.Request``).

    ``clients`` > 0 is a closed loop of that many clients, each sending
    its next request when its last completes; otherwise ``arrivals``
    (offsets in seconds from :meth:`run`'s start) is an open loop.
    """

    def __init__(self, submit, step, lengths, *, clients: int = 0,
                 arrivals=None, clock=time.perf_counter, tick=None):
        if bool(clients) == (arrivals is not None):
            raise ValueError("give either clients or arrivals")
        self.submit, self.step = submit, step
        self.lengths = iter(lengths)
        self.clients = clients
        self.arrivals = arrivals if arrivals is None else iter(arrivals)
        self.clock = clock
        self.tick = tick or (lambda: None)
        self.sent: list = []        # (due, submitted, handle), in order
        self._slots: list = [None] * clients
        self._next_due = None       # open loop: offset of the next arrival
        self.t0 = None

    def _send(self, due: float) -> object:
        handle = self.submit(*next(self.lengths))
        self.sent.append((due, self.clock(), handle))
        return handle

    def _offer(self, now: float) -> None:
        if self.clients:
            for i, h in enumerate(self._slots):
                if h is None or h.done:
                    # Due the moment the last answer was complete.
                    due = h.token_times[-1] if h and h.token_times else now
                    self._slots[i] = self._send(due)
            return
        while self.t0 + self._next_due <= now:
            self._send(self.t0 + self._next_due)
            self._next_due = next(self.arrivals)

    def run(self, until) -> None:
        """Offer load and step the engine until ``until(load)`` is true.
        Can be called again to go on from where it stopped."""
        if self.t0 is None:
            self.t0 = self.clock()
            if not self.clients:
                self._next_due = next(self.arrivals)
        while not until(self):
            self._offer(self.clock())
            self.tick()
            if not self.step() and not self.clients:
                # Idle and ahead of the arrivals: wait for the next one.
                time.sleep(max(0.0, min(
                    self.t0 + self._next_due - self.clock(), 0.01)))

    def completed(self) -> int:
        return sum(1 for _, _, h in self.sent if h.done)


def window_stats(sent, t_open: float, t_close: float) -> dict:
    """What a window ``[t_open, t_close)`` saw of the requests ``sent``
    (``(due, submitted, handle)``).

    Tokens and the gaps between a request's tokens count where the
    (later) stamp falls inside the window, whichever request they belong
    to. A request is ``attempted`` if it was due inside the window; its
    time to first token runs from when it was due.
    """
    stamps, gaps = 0, []
    for _, _, h in sent:
        t = np.asarray(h.token_times, float)
        inside = (t >= t_open) & (t < t_close)
        stamps += int(inside.sum())
        if len(t) > 1:
            gaps.extend(np.diff(t)[inside[1:]] * 1e3)
    due = [(d, s, h) for d, s, h in sent if t_open <= d < t_close]
    ttft = [(h.token_times[0] - d) * 1e3 for d, _, h in due
            if h.token_times]
    return {
        "tokens": stamps,
        "tok_s": stamps / (t_close - t_open),
        "itl_ms": gaps,
        "attempted": len(due),
        "due": due,
        "ttft_ms": ttft,
        "lateness_ms": [(s - d) * 1e3 for d, s, _ in due],
    }
