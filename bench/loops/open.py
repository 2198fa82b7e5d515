"""``open``: requests are due at seeded times at ``rate_per_s``; each goes
to the next free edge (``session.infer``), and its latency runs from when
it was due.

The gaps between requests are the quantiles of an exponential distribution
at the offered rate, one set for every seed, in an order drawn from the
seed, scaled to fill the window. Every seed offers the same number of
requests and the same gaps.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import loadgen


def offsets(rate: float, seconds: float,
            rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of an open loop at ``rate``."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.cumsum(gaps) - gaps[0]


def workers(run, sessions, traffic, image, rng):
    due = iter(run.new(list(run.t0 + offsets(traffic["rate_per_s"],
                                             run.seconds, rng))))

    def edge(sess):
        while True:
            with run.lock:
                r = next(due, None)
            if r is None:
                return
            wait = r.due - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.wait_due"):
                    time.sleep(wait)
            img = image(r.rid)
            r.start = time.perf_counter()
            if not loadgen.call([r], lambda: [sess.infer(img)]):
                return
    return [lambda s=s: edge(s) for s in sessions]
