"""``closed``: each edge sends its next request when the last one has
returned (``session.infer``), until the window closes."""
from __future__ import annotations

import time

from bench import loadgen


def workers(run, sessions, traffic, image, rng):
    def edge(sess):
        while time.perf_counter() < run.deadline:
            r, = run.new([time.perf_counter()])
            img = image(r.rid)
            r.due = r.start = time.perf_counter()
            if not loadgen.call([r], lambda: [sess.infer(img)]):
                return
    return [lambda s=s: edge(s) for s in sessions]
