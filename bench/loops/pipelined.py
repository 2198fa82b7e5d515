"""``pipelined``: each edge keeps ``chunk`` requests in flight through
``session.infer_many`` and sends the next chunk when the last has returned,
until the window closes."""
from __future__ import annotations

import time

from bench import loadgen


def workers(run, sessions, traffic, image, rng):
    chunk = traffic["chunk"]

    def edge(sess):
        while time.perf_counter() < run.deadline:
            reqs = run.new([time.perf_counter()] * chunk)
            imgs = [image(r.rid) for r in reqs]
            t = time.perf_counter()
            for r in reqs:
                r.due = r.start = t
            if not loadgen.call(reqs, lambda: sess.infer_many(imgs)):
                return
    return [lambda s=s: edge(s) for s in sessions]
