"""Async continuous-batching serve runtime (DESIGN.md §13).

``ServeEngine`` (engine.py) is a synchronous call-in/call-out wrapper: one
caller, one batch, one blocking device round-trip.  This module is the
serving *process* around it — the piece that lets a single engine sustain
interleaved IF/IS/RF/RS traffic with concurrent streaming updates:

* **admission** — :meth:`ServeRuntime.submit` appends a request (its own
  semantics flag, ef, k, and optional deadline) to a bounded FIFO; requests
  whose deadline already passed are rejected *at admission* with
  :class:`DeadlineExceeded` (never silently dropped), and the bound gives
  callers backpressure instead of an unbounded queue;
* **coalescing** — the dispatcher packs the longest run of compatible
  pending requests (same static ``(ef, k)`` compile key; semantics are
  runtime state, DESIGN.md §10) into one micro-batch, padded to a
  :data:`~repro.serve.engine.BATCH_BUCKETS` shape, so any traffic mix hits
  the one compiled ``search_mixed`` program per bucket;
* **dispatch overlap** — the dispatcher thread only *launches* the device
  program (jax dispatch is asynchronous) and hands the in-flight batch to a
  completion thread that blocks and resolves futures, so host-side packing
  of batch ``i+1`` overlaps device execution of batch ``i``;
* **snapshot semantics** — updates are functional: the writer builds a new
  :class:`~repro.core.store.IndexStore` and swaps the engine's index
  *reference* atomically.  Query batches pin the index once at dequeue
  time, so an in-flight batch always reads one consistent snapshot, and
  FIFO order gives the external contract: a query admitted before a write
  answers against the pre-write snapshot, one admitted after against the
  post-write snapshot — never a torn mix;
* **fleet health** — :class:`FleetServeMonitor` wires the sharded path's
  per-shard probe timings (:func:`~repro.core.sharded.make_shard_probe_fns`)
  into :class:`~repro.ft.straggler.FleetMonitor` slow-shard detection and
  :func:`~repro.ft.elastic.plan_serve_rescale` replica planning;
* **spans** — each thread's loop is tiled by per-batch spans
  (:func:`repro.trace.span`), recorded only while a profiler trace is
  active: the dispatcher is always inside one of ``serve.dequeue``,
  ``serve.pack``, ``serve.launch``, ``serve.inflight_put`` or
  ``serve.write``, the completer inside ``serve.device_wait`` or
  ``serve.resolve`` while it holds a batch.  Every span of one batch
  carries its sequence number ``batch``.  No span is opened per request:
  per-request facts (queue wait, time spent in :meth:`ServeRuntime.submit`)
  are stamped on the batch's ``serve.pack`` span.

Every row of a fused search batch is bitwise independent of the rest of the
batch (DESIGN.md §10), which is what makes continuous batching *exact*
here: however the coalescer slices the stream, each request's answer equals
a direct ``search_mixed`` call on its pinned snapshot, bit for bit
(tests/test_serve_runtime.py pins this).
"""
from __future__ import annotations

import collections
import dataclasses
import math
import random
import threading
import time
import queue as _queue
from concurrent.futures import Future
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ft.elastic import RescalePlan, plan_serve_rescale
from repro.ft.straggler import FleetMonitor, StragglerConfig
from repro.serve.engine import ServeEngine, bucket_batch_size
from repro.trace import compiles, count_compiles, span


_LAT_RESERVOIR_CAP = 4096
# A span still open when a profiler trace starts is not recorded, so the
# dispatcher's waits are cut into slices of at most this many seconds, each a
# span of its own: its loop is then tiled from a trace's first instant on.
_WAIT_SLICE_S = 0.1


class LatencyReservoir:
    """Fixed-size uniform sample of a latency stream (Vitter's Algorithm R).

    ``stats()`` wants percentiles over the whole run, but a long-lived
    runtime must not grow host memory with traffic.  The first ``cap``
    samples are kept verbatim; after that each new sample replaces a
    uniformly random held slot with probability ``cap / seen``, which keeps
    the held set a uniform random sample of everything ever offered.  The
    RNG is seeded so repeated runs report identical percentiles.
    """

    def __init__(self, cap: int = _LAT_RESERVOIR_CAP, *, seed: int = 0):
        if cap <= 0:
            raise ValueError(f"reservoir cap must be positive, got {cap}")
        self.cap = cap
        self.seen = 0
        self._rng = random.Random(seed)
        self._sample: list[float] = []

    def offer(self, x: float) -> None:
        self.seen += 1
        if len(self._sample) < self.cap:
            self._sample.append(x)
        else:
            j = self._rng.randrange(self.seen)
            if j < self.cap:
                self._sample[j] = x

    def extend(self, xs) -> None:
        for x in xs:
            self.offer(x)

    def __len__(self) -> int:
        return len(self._sample)

    def __iter__(self):
        return iter(self._sample)


class DeadlineExceeded(Exception):
    """A request's deadline passed before it could be dispatched.

    Raised *into the request's future* both at admission (deadline already
    in the past) and at dequeue (expired while queued) — an expired request
    is always answered with this error, never silently dropped.
    """


class QueueFull(Exception):
    """Admission bound hit: the caller must shed load or retry later."""


class ServeReply(NamedTuple):
    """One request's answer + the provenance the consistency tests pin."""

    ids: np.ndarray        # (k,) int32 global ids, -1 padded
    dist: np.ndarray       # (k,) f32 squared distances
    latency_s: float       # submit → future-resolution wall time
    index: Any             # the pinned UGIndex snapshot this answered against


@dataclasses.dataclass
class RuntimeConfig:
    max_batch: int = 256     # coalescer cap (one micro-batch's request count)
    max_queue: int = 4096    # admission bound (pending requests + writes)
    max_inflight: int = 2    # dispatched-but-unresolved micro-batches
    default_ef: int = 64
    default_k: int = 10


@dataclasses.dataclass
class _Query:
    q_v: jnp.ndarray         # (d,)
    q_int: jnp.ndarray       # (2,)
    flag: int                # FLAG_IF | FLAG_IS (runtime semantics)
    ef: int
    k: int
    deadline: float | None   # absolute clock() time, None = no deadline
    future: Future
    t_submit: float


@dataclasses.dataclass
class _Batch:
    seq: int                 # batch sequence number, the spans' ``batch``
    queries: list[_Query]
    admit_s: float           # seconds callers spent in submit() since the
                             # previous batch was dequeued


@dataclasses.dataclass
class _Write:
    kind: str                # "upsert" | "remove"
    payload: tuple
    future: Future
    t_submit: float


class ServeRuntime:
    """Continuous-batching loop over a :class:`ServeEngine`.

    Two execution modes share all of the machinery:

    * **threaded** — :meth:`start` spawns the dispatcher + completer pair;
      :meth:`stop` drains and joins them.  This is the serving mode
      (``launch/serve.py --async``, ``bench_serve``).
    * **inline** — :meth:`run_until_idle` pumps the same dequeue → coalesce
      → dispatch → complete pipeline on the caller's thread until the queue
      is empty.  Deterministic, thread-free; what most unit tests drive.

    The engine's ``search_backend``/``search_width`` are honored; writes go
    through ``ServeEngine.upsert``/``remove`` and therefore reuse the
    single-sync :func:`~repro.serve.engine.upsert_chunk_plan` and the
    bucketed update programs (DESIGN.md §11).
    """

    def __init__(
        self,
        engine: ServeEngine,
        config: RuntimeConfig = RuntimeConfig(),
        *,
        clock=time.monotonic,
    ):
        if engine.index is None:
            raise ValueError("engine has no index attached")
        self.engine = engine
        self.cfg = config
        self.clock = clock
        self._cv = threading.Condition()
        self._pending: collections.deque = collections.deque()
        self._inflight: _queue.Queue = _queue.Queue(maxsize=config.max_inflight)
        self._dispatcher: threading.Thread | None = None
        self._completer: threading.Thread | None = None
        self._stopping = False
        self._stats_lock = threading.Lock()
        self._latencies = LatencyReservoir()
        self._completed = 0
        self._rejected = 0
        self._writes = 0
        # Wall clock for qps accounts *active* serving windows only: time
        # between start()/stop() pairs plus time spent inside
        # run_until_idle().  Anchoring at construction (the old behaviour)
        # charged queries for index-build / idle time and made stop/start
        # cycles report qps against the wrong window.
        self._t_start: float | None = None
        self._wall_accum = 0.0
        self._admit_s = 0.0          # guarded by _cv
        self._batches = 0            # dequeued query batches (dispatcher)
        # Sequence numbers of launched batches, in launch order: the
        # completer resolves batches in that order and pops them.
        self._launched: collections.deque = collections.deque()
        count_compiles()

    # ------------------------------------------------------------ admission
    def submit(
        self,
        q_v,
        q_int,
        sem,
        *,
        ef: int | None = None,
        k: int | None = None,
        deadline: float | None = None,
    ) -> Future:
        """Admit one query; returns a future resolving to a :class:`ServeReply`.

        ``sem`` is a :class:`~repro.core.Semantics` or a raw flag int;
        ``deadline`` is an absolute ``clock()`` time.  An already-expired
        request is rejected immediately (future carries
        :class:`DeadlineExceeded`); a full queue raises :class:`QueueFull`
        synchronously so the caller sees backpressure.
        """
        from repro.core import as_sem_flags

        t_enter = time.perf_counter()
        fut: Future = Future()
        now = self.clock()
        flag = int(np.asarray(as_sem_flags([sem], 1))[0])
        if deadline is not None and deadline <= now:
            self._reject(fut, DeadlineExceeded(
                f"deadline {deadline:.3f} already passed at admission "
                f"({now:.3f})"))
            return fut
        req = _Query(
            jnp.asarray(q_v), jnp.asarray(q_int), flag,
            int(ef if ef is not None else self.cfg.default_ef),
            int(k if k is not None else self.cfg.default_k),
            deadline, fut, now,
        )
        self._enqueue(req, t_enter)
        return fut

    def submit_upsert(self, x, intervals) -> Future:
        """Admit a streaming insert; future resolves to the inserted count.
        FIFO position defines its snapshot boundary: queries admitted before
        it answer pre-write, queries admitted after answer post-write."""
        fut: Future = Future()
        self._enqueue(_Write(
            "upsert", (jnp.atleast_2d(jnp.asarray(x)),
                       jnp.atleast_2d(jnp.asarray(intervals))),
            fut, self.clock(),
        ))
        return fut

    def submit_remove(self, ids, *, repair: bool = True) -> Future:
        """Admit a streaming delete; future resolves to the removed count."""
        fut: Future = Future()
        self._enqueue(_Write("remove", (jnp.asarray(ids), repair),
                             fut, self.clock()))
        return fut

    def _enqueue(self, item, t_enter: float | None = None) -> None:
        """Append to the FIFO; ``t_enter`` (``perf_counter`` at entry to
        :meth:`submit`) adds the caller's admission time to the next
        batch's ``admit_s``."""
        with self._cv:
            if t_enter is not None:
                self._admit_s += time.perf_counter() - t_enter
            if self._stopping:
                raise RuntimeError("runtime is stopping; admission closed")
            if len(self._pending) >= self.cfg.max_queue:
                raise QueueFull(
                    f"admission queue at bound {self.cfg.max_queue}")
            self._pending.append(item)
            self._cv.notify()

    def _reject(self, fut: Future, exc: Exception) -> None:
        with self._stats_lock:
            self._rejected += 1
        fut.set_exception(exc)

    # ----------------------------------------------------------- coalescing
    def _next_work(self, block: bool):
        """Dequeue the next unit of work in FIFO order: either one write op
        or a :class:`_Batch` of the longest head run of queries sharing a
        compile key, capped at ``max_batch``.  Returns None when idle
        (inline mode) or stopped."""
        while True:
            with span("serve.dequeue") as sp, self._cv:
                waited = not self._pending
                sp.set_metadata(waited=waited)
                if waited:
                    if not block or self._stopping:
                        return None
                    self._cv.wait(_WAIT_SLICE_S)
                    if not self._pending:
                        continue
                head = self._pending[0]
                if isinstance(head, _Write):
                    self._pending.popleft()
                    return head
                key = (head.ef, head.k)
                queries = []
                while (
                    self._pending
                    and isinstance(self._pending[0], _Query)
                    and (self._pending[0].ef, self._pending[0].k) == key
                    and len(queries) < self.cfg.max_batch
                ):
                    queries.append(self._pending.popleft())
                batch = _Batch(self._batches, queries, self._admit_s)
                self._batches += 1
                self._admit_s = 0.0
                sp.set_metadata(batch=batch.seq)
                return batch

    def _launch(self, batch: _Batch):
        """Expire dead requests, pin the snapshot, pack + pad the micro-batch
        and *launch* the device program (no blocking here — jax dispatch is
        asynchronous; the completer owns the block)."""
        from repro.core import FLAG_IF
        from repro.core.search import search_mixed

        with span("serve.pack", batch=batch.seq) as sp:
            c0 = compiles()
            now = self.clock()
            live = []
            for r in batch.queries:
                if r.deadline is not None and r.deadline <= now:
                    self._reject(r.future, DeadlineExceeded(
                        f"deadline expired in queue ({now - r.t_submit:.3f}s "
                        f"after admission)"))
                else:
                    live.append(r)
            if not live:
                sp.set_metadata(live=0)
                return None
            index = self.engine.index       # pin the snapshot at dequeue time
            ef, k = live[0].ef, live[0].k
            B = len(live)
            qv = jnp.stack([r.q_v for r in live])
            qint = jnp.stack([r.q_int for r in live])
            flags = jnp.asarray([r.flag for r in live], jnp.int32)
            Bp = bucket_batch_size(B)
            if Bp != B:
                pad = Bp - B
                qv = jnp.concatenate(
                    [qv, jnp.zeros((pad, qv.shape[1]), qv.dtype)])
                dead = jnp.broadcast_to(
                    jnp.asarray([2.0, -2.0], qint.dtype), (pad, 2))
                qint = jnp.concatenate([qint, dead])
                flags = jnp.concatenate(
                    [flags, jnp.full((pad,), FLAG_IF, jnp.int32)])
            wait_ms = [1e3 * (now - r.t_submit) for r in live]
            sp.set_metadata(
                live=B, bucket=Bp, queue_wait_ms_max=max(wait_ms),
                queue_wait_ms_med=float(np.median(wait_ms)),
                admit_s=batch.admit_s, compiles=compiles() - c0)
        with span("serve.launch", batch=batch.seq) as sp:
            c0 = compiles()
            res = search_mixed(
                index.store, qv, qint, flags, ef=ef, k=k,
                backend=self.engine.search_backend,
                width=self.engine.search_width,
            )
            sp.set_metadata(compiles=compiles() - c0)
        self._launched.append(batch.seq)
        return live, res, index

    def _complete(self, inflight) -> None:
        """Block on one in-flight micro-batch and resolve its futures."""
        from repro.core.search import frontier_width

        live, res, index = inflight
        seq = self._launched.popleft()
        with span("serve.device_wait", batch=seq):
            ids = np.asarray(res.ids)       # blocks until the batch is done
            dist = np.asarray(res.dist)
        with span("serve.resolve", batch=seq) as sp:
            steps = np.asarray(res.steps)[:len(live)]
            sp.set_metadata(
                live=len(live), bucket=ids.shape[0],
                width=frontier_width(self.engine.search_width, live[0].ef,
                                     self.engine.search_backend),
                iters=int(res.iters), steps_sum=int(steps.sum()),
                steps_max=int(steps.max()))
            now = self.clock()
            lats = []
            for i, r in enumerate(live):
                lat = now - r.t_submit
                lats.append(lat)
                r.future.set_result(ServeReply(ids[i], dist[i], lat, index))
        with self._stats_lock:
            self._completed += len(live)
            self._latencies.extend(lats)

    def _apply_write(self, w: _Write) -> None:
        """Run one write through the engine.  ``ServeEngine.upsert/remove``
        build the new index functionally and swap ``engine.index`` — an
        atomic reference store, so concurrent dequeues see either the old
        or the new snapshot, never a mix."""
        try:
            rows = w.payload[0].shape[0] if w.kind == "upsert" else w.payload[0].size
            with span("serve.write", kind=w.kind, rows=int(rows)):
                if w.kind == "upsert":
                    x, ivs = w.payload
                    out = self.engine.upsert(None, ivs, x=x)
                else:
                    ids, repair = w.payload
                    out = self.engine.remove(ids, repair=repair)
            with self._stats_lock:
                self._writes += 1
            w.future.set_result(out)
        except Exception as e:  # noqa: BLE001 — surface to the submitter
            w.future.set_exception(e)

    # ------------------------------------------------------------ execution
    def run_until_idle(self) -> int:
        """Inline mode: pump dequeue → dispatch → complete until the queue is
        empty.  Returns the number of work units processed.  The pump's own
        wall time counts toward the qps window (stats())."""
        done = 0
        t0 = self.clock()
        try:
            while True:
                work = self._next_work(block=False)
                if work is None:
                    return done
                done += 1
                if isinstance(work, _Write):
                    self._apply_write(work)
                else:
                    inflight = self._launch(work)
                    if inflight is not None:
                        self._complete(inflight)
        finally:
            with self._stats_lock:
                self._wall_accum += self.clock() - t0

    def _dispatch_loop(self) -> None:
        while True:
            work = self._next_work(block=True)
            if work is None:
                break
            if isinstance(work, _Write):
                self._apply_write(work)
            else:
                inflight = self._launch(work)
                while inflight is not None:          # backpressure at cap
                    with span("serve.inflight_put", batch=work.seq):
                        try:
                            self._inflight.put(inflight, timeout=_WAIT_SLICE_S)
                            inflight = None
                        except _queue.Full:
                            pass
        self._inflight.put(None)                   # completer shutdown

    def _complete_loop(self) -> None:
        while True:
            inflight = self._inflight.get()
            if inflight is None:
                break
            self._complete(inflight)

    def start(self) -> "ServeRuntime":
        if self._dispatcher is not None:
            raise RuntimeError("runtime already started")
        with self._stats_lock:
            self._t_start = self.clock()
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="serve-dispatch", daemon=True)
        self._completer = threading.Thread(
            target=self._complete_loop, name="serve-complete", daemon=True)
        self._dispatcher.start()
        self._completer.start()
        return self

    def stop(self) -> None:
        """Drain the queue, then join both threads."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        if self._dispatcher is not None:
            self._dispatcher.join()
            self._completer.join()
            self._dispatcher = self._completer = None
        with self._stats_lock:
            if self._t_start is not None:
                self._wall_accum += self.clock() - self._t_start
                self._t_start = None
        # _stopping only closes admission once threads exist; inline-mode
        # users never set it, so a stopped runtime can be started again.
        self._stopping = False

    def __enter__(self) -> "ServeRuntime":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Serving counters + latency percentiles over the current run.

        ``qps`` is completed requests over the *active* wall time — closed
        start/stop windows plus run_until_idle() pumps plus the currently
        open start() window, if any.  Percentiles come from a bounded
        uniform reservoir of the per-request latencies."""
        with self._stats_lock:
            lats = sorted(self._latencies)
            completed = self._completed
            rejected = self._rejected
            writes = self._writes
            wall = self._wall_accum
            if self._t_start is not None:
                wall += self.clock() - self._t_start
        return {
            "completed": completed,
            "rejected": rejected,
            "writes": writes,
            "qps": completed / max(wall, 1e-9),
            "p50_ms": 1e3 * _pctl(lats, 0.50),
            "p99_ms": 1e3 * _pctl(lats, 0.99),
        }


def _pctl(sorted_xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest element with at least ``q`` of
    the sample at or below it, i.e. index ``ceil(q*n) - 1``.  (``int(q*n)``
    sits one rank high: it maps the median of [1, 2] to 2.)"""
    if not sorted_xs:
        return 0.0
    n = len(sorted_xs)
    i = min(max(math.ceil(q * n) - 1, 0), n - 1)
    return sorted_xs[i]


# --------------------------------------------------------------------------
# Fleet health: straggler probing + elastic replica planning (sharded path)
# --------------------------------------------------------------------------
class FleetServeMonitor:
    """Per-shard step timing → slow-shard mitigation + replica planning.

    One :class:`~repro.ft.straggler.StepTimer` slot per shard of a
    :class:`~repro.core.sharded.ShardedIndex`.  :meth:`probe` times each
    shard's local search (the callables from
    :func:`~repro.core.sharded.make_shard_probe_fns` — the same program the
    ``shard_map`` step runs per shard) and feeds the fleet monitor;
    :meth:`report` turns the timings into straggler ids, per-shard
    mitigation advice, and a :func:`~repro.ft.elastic.plan_serve_rescale`
    replica plan for the healthy capacity.
    """

    def __init__(
        self,
        n_shards: int,
        n_devices: int,
        cfg: StragglerConfig = StragglerConfig(),
    ):
        if n_devices % n_shards:
            raise ValueError(
                f"{n_devices} devices not divisible by {n_shards} shards")
        self.n_shards = n_shards
        self.n_devices = n_devices
        self.fleet = FleetMonitor(n_shards, cfg)

    def record(self, shard: int, seconds: float) -> None:
        self.fleet.record(shard, seconds)

    def probe(self, shard_fns, q_v, q_int, sem_flags) -> list[float]:
        """Time one local-search step per shard and record the fleet."""
        times = []
        for s, fn in enumerate(shard_fns):
            t0 = time.perf_counter()
            out = fn(q_v, q_int, sem_flags)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            times.append(dt)
            self.fleet.record(s, dt)
        return times

    def report(self) -> dict:
        """Fleet health snapshot: stragglers, mitigations, replica plans."""
        slow = self.fleet.stragglers()
        per_shard = self.n_devices // self.n_shards
        healthy = self.n_devices - len(slow) * per_shard
        plan = plan_serve_rescale(self.n_devices, self.n_shards)
        degraded: RescalePlan | None = None
        if slow and healthy >= self.n_shards:
            # treat each straggling shard's device group as lost capacity:
            # the replica plan for what remains is what the launcher would
            # rescale to while the slow group recompiles/recovers
            degraded = plan_serve_rescale(healthy, self.n_shards)
        return {
            "stragglers": slow,
            "recommendations": self.fleet.recommendations(),
            "plan": plan,
            "degraded_plan": degraded,
        }
