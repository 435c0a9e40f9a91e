"""The one load generator: closed-loop clients driven through
``ServeRuntime`` from a few submitting threads.

It reads only a traffic file's parameters.  Every request is recorded in a
:class:`Record`: when it was due, when it was submitted, when its future
resolved, the snapshot its answer names and the answer.  Callbacks run on
the runtime's completion thread and only store numbers.
"""
from __future__ import annotations

import queue
import threading
import time

from concurrent.futures import Future

import numpy as np
from repro.serve.runtime import QueueFull

clock = time.perf_counter


class Record:
    """Per-request arrays, filled by slot.  The generator assigns slots
    under its lock; the completion thread writes only its own slot."""

    def __init__(self, cap: int, k: int):
        self.n = 0
        self.q = np.zeros(cap, np.int64)
        self.due = np.full(cap, np.nan)
        self.sub = np.full(cap, np.nan)
        self.done = np.full(cap, np.nan)
        self.seq = np.full(cap, -1, np.int32)
        self.err = np.zeros(cap, bool)
        self.ids = np.full((cap, k), -1, np.int32)
        self.dist = np.full((cap, k), np.inf, np.float32)
        self._left = threading.Semaphore(0)

    def take(self) -> int:
        if self.n == len(self.q):
            raise RuntimeError(f"request record full at {self.n}")
        self.n += 1
        return self.n - 1

    def finished(self, slot, fut, snaps):
        t = clock()
        exc = fut.exception()
        if exc is None:
            reply = fut.result()
            self.ids[slot] = reply.ids
            self.dist[slot] = reply.dist
            self.seq[slot] = snaps.get(id(reply.index), -1)
        else:
            self.err[slot] = True
        self.done[slot] = t
        self._left.release()

    def wait_all(self, deadline: float) -> int:
        """Wait until every submitted request resolved or the deadline
        passed; returns how many are still open."""
        for _ in range(self.n):
            if not self._left.acquire(timeout=max(deadline - clock(), 0.0)):
                break
        return int(np.sum(np.isnan(self.done[:self.n])))


class Generator:
    """Submits queries from the pool in order, round-robin over it."""

    def __init__(self, rt, data, traffic, rec, snaps):
        self.rt, self.data, self.rec, self.snaps = rt, data, rec, snaps
        self.ef, self.k = int(traffic["ef"]), int(traffic["k"])
        self.next_q = 0
        self.lock = threading.Lock()
        self.ready: queue.SimpleQueue = queue.SimpleQueue()

    def submit(self, due: float, client: int | None = None) -> None:
        d, rec = self.data, self.rec
        with self.lock:
            qi = self.next_q % len(d["qv"])
            self.next_q += 1
            slot = rec.take()
        rec.q[slot], rec.due[slot] = qi, due
        rec.sub[slot] = clock()
        fut = Future()
        try:
            fut = self.rt.submit(d["qv"][qi], d["qint"][qi],
                                 int(d["qflag"][qi]), ef=self.ef, k=self.k)
        except QueueFull as e:   # refused at admission: a failed request
            fut.set_exception(e)
        fut.add_done_callback(lambda f: self._finished(slot, f, client))

    def _finished(self, slot, fut, client):
        self.rec.finished(slot, fut, self.snaps)
        if client is not None:
            self.ready.put(client)

    def closed(self, clients: int, threads: int, start, t_end_of,
               drain: float) -> float:
        """``clients`` callers, each sending its next query when its last
        one is answered, resubmitted by ``threads`` threads.  Every first
        query is queued before the runtime starts, so the coalescer sees
        them all.  Returns the window start."""
        for c in range(clients):
            self.submit(0.0, c)
        t0 = start()
        self.rec.due[:clients] = t0
        self.rec.sub[:clients] = t0
        t_end = t_end_of(t0)

        def resubmit():
            while True:
                try:
                    c = self.ready.get(timeout=max(t_end - clock(), 0.0) + drain)
                except queue.Empty:  # nothing answered for ``drain`` seconds
                    return
                now = clock()
                if now >= t_end:
                    return
                self.submit(now, c)

        others = [threading.Thread(target=resubmit, name=f"loadgen-{i}", daemon=True)
                  for i in range(1, threads)]
        for t in others:
            t.start()
        resubmit()
        for t in others:
            t.join(drain)
        return t0
