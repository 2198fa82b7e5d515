"""The one load generator: drives a cell's edge sessions as its traffic file
says, and records every request.

A traffic file (``traffic/<name>.json``) holds only parameters. Its
``loop`` names the module that paces the edges, ``loops/<loop>.py``; the
module's ``workers(run, sessions, traffic, image, rng)`` returns one
callable per edge thread. The loops of this directory:

``closed``
    each edge sends its next request when the last one has returned
    (``session.infer``).
``pipelined``
    each edge keeps ``chunk`` requests in flight through
    ``session.infer_many`` and sends the next chunk when the last has
    returned.
``open``
    requests are due at seeded times at ``rate_per_s``, each goes to the
    next free edge, and its latency runs from when it was due.

Keys every traffic file has:

``edges``
    socket edge sessions, each a thread of this process.
``pool``
    distinct base images; request ``i`` sends base ``i mod pool``,
    brightened by ``i // pool`` steps, so no two requests are alike.
``warm_s``
    seconds of the same traffic run before the window and not measured.
"""
from __future__ import annotations

import importlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

import jax
import numpy as np


@dataclass
class Request:
    rid: int
    due: float            # when it was due (open loop) or sent (host clock)
    start: float = 0.0    # when an edge began it
    done: float = 0.0     # when its answer was back
    logits: Optional[np.ndarray] = None
    t_edge: Optional[float] = None
    error: Optional[str] = None


@dataclass
class LoadRun:
    """Every request of one window, and the window's clock readings."""
    t0: float
    seconds: float
    ids: Iterator[int]
    requests: List[Request] = field(default_factory=list)
    threads: List[threading.Thread] = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)

    @property
    def deadline(self) -> float:
        return self.t0 + self.seconds

    def new(self, dues: List[float]) -> List[Request]:
        """Record one new request per due time, ids in order."""
        with self.lock:
            reqs = [Request(next(self.ids), d) for d in dues]
            self.requests.extend(reqs)
        return reqs

    def join(self, timeout: float) -> None:
        end = time.perf_counter() + timeout
        for t in self.threads:
            t.join(max(0.0, end - time.perf_counter()))
        alive = [t.name for t in self.threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"edge threads still running: {alive}")

    @property
    def finished(self) -> List[Request]:
        return [r for r in self.requests if r.error is None and r.done]

    @property
    def failed(self) -> List[Request]:
        return [r for r in self.requests if r.error is not None]


def _fault_free(res: Dict) -> bool:
    f = res.get("fault") or {}
    return not any(f.get(k) for k in ("faults", "retries", "migrations",
                                      "fallback"))


def call(reqs: List[Request], send: Callable[[], List[Dict]]) -> bool:
    """Send ``reqs`` through ``send`` (a session call that answers them in
    order) and record each answer; False where the call raised."""
    try:
        with jax.profiler.TraceAnnotation("bench.request"):
            out = send()
    except Exception as e:                               # noqa: BLE001
        for r in reqs:
            r.error = f"{type(e).__name__}: {e}"
        return False
    t = time.perf_counter()
    for r, res in zip(reqs, out):
        r.done, r.logits, r.t_edge = t, res["logits"][0], res["t_edge"]
        if not _fault_free(res):
            r.error = f"fault record {res['fault']}"
    return True


def loop_module(name: str):
    """The module that paces a traffic file's ``loop``."""
    return importlib.import_module(f"bench.loops.{name}")


def start(sessions, traffic: Dict, image: Callable[[int], np.ndarray],
          seconds: float, rng: np.random.Generator,
          first_id: int = 0, step: int = 1) -> LoadRun:
    """Start the edges on a window of ``seconds``; returns at once. Request
    ids count from ``first_id`` by ``step`` (the warm-up counts down)."""
    run = LoadRun(t0=time.perf_counter(), seconds=seconds,
                  ids=iter(range(first_id, first_id + step * 10**9, step)))
    work = loop_module(traffic["loop"]).workers(run, sessions, traffic,
                                                image, rng)
    for e, fn in enumerate(work):
        run.threads.append(threading.Thread(target=fn, daemon=True,
                                            name=f"bench-edge-{e}"))
    for t in run.threads:
        t.start()
    return run


def lateness(run: LoadRun) -> Dict[str, float]:
    """How late the edges began the finished requests (start - due), ms."""
    late = np.array([r.start - r.due for r in run.finished])
    if not late.size:
        return {}
    return {"late_p50_ms": float(np.percentile(late, 50)) * 1e3,
            "late_p99_ms": float(np.percentile(late, 99)) * 1e3,
            "late_max_ms": float(late.max()) * 1e3}
