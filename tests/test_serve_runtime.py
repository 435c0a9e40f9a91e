"""Async serve-runtime suite (DESIGN.md §13).

The continuous-batching contracts this pins:

* **exactness** — however the coalescer slices the request stream, every
  reply is bitwise-equal to a direct ``search_mixed`` call on the reply's
  pinned snapshot (row independence of the fused batch, DESIGN.md §10);
* **snapshot consistency** — a query admitted before a write answers
  against the pre-write snapshot, one admitted after against the post-write
  snapshot, never a torn mix (the writer swaps the index *reference*;
  readers pin it once at dequeue);
* **deadlines** — expired requests are answered with
  :class:`DeadlineExceeded` (at admission or at dequeue), never silently
  dropped, and they are counted in ``stats()["rejected"]``;
* **backpressure** — admission past ``max_queue`` raises
  :class:`QueueFull` synchronously;
* **single-sync upserts** — ``ServeEngine.upsert`` reads ``index.n``
  exactly once per call, and every chunk of every call lands on a
  :data:`~repro.serve.engine.BATCH_BUCKETS` shape.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FLAG_IF, FLAG_IS, Semantics, UGConfig, UGIndex
from repro.core import intervals as iv
from repro.core.search import search_mixed
from repro.serve import (
    DeadlineExceeded,
    QueueFull,
    RuntimeConfig,
    ServeEngine,
    ServeRuntime,
)
from repro.serve.engine import BATCH_BUCKETS, bucket_batch_size, upsert_chunk_plan

CFG = UGConfig(ef_spatial=16, ef_attribute=32, max_edges_if=12,
               max_edges_is=12, iterations=2, repair_width=8,
               exact_spatial=True, block=512)


_INDEX_CACHE: dict = {}


def small_index(n=300, d=12, seed=5):
    """Built once per (n, d, seed) and shared: the index is immutable (every
    update is functional and swaps the engine's *reference*), so engines in
    different tests can all attach the same snapshot safely."""
    key = (n, d, seed)
    if key not in _INDEX_CACHE:
        k1, k2 = jax.random.split(jax.random.key(seed))
        x = jax.random.normal(k1, (n, d))
        ints = iv.sample_uniform_intervals(k2, n)
        _INDEX_CACHE[key] = UGIndex.build(x, ints, CFG)
    return _INDEX_CACHE[key]


def make_engine(**kw):
    eng = ServeEngine(None, None)  # no model: q_v/x always precomputed here
    eng.attach_index(small_index(**kw))
    return eng


def make_queries(nq, d=12, seed=11):
    k1, k2 = jax.random.split(jax.random.key(seed))
    qv = jax.random.normal(k1, (nq, d))
    c = jax.random.uniform(k2, (nq, 1))
    qi = jnp.concatenate([jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)],
                         axis=1)
    flags = [FLAG_IF if i % 2 else FLAG_IS for i in range(nq)]
    return qv, qi, flags


class FakeClock:
    """Injectable monotonic clock for deterministic deadline tests."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def direct_rows(index, qv, qi, flags, *, ef=64, k=10, sel=None):
    """Reference answers: one padded search_mixed call per selected row set,
    exactly the engine's bucket-padding recipe."""
    res, B = direct_search(index, qv, qi, flags, ef=ef, k=k, sel=sel)
    return np.asarray(res.ids)[:B], np.asarray(res.dist)[:B]


def direct_search(index, qv, qi, flags, *, ef=64, k=10, sel=None):
    """The padded ``search_mixed`` result of the selected rows, and how many
    of its rows are live."""
    idxs = list(range(qv.shape[0])) if sel is None else list(sel)
    B = len(idxs)
    q = jnp.stack([qv[i] for i in idxs])
    w = jnp.stack([qi[i] for i in idxs])
    f = jnp.asarray([flags[i] for i in idxs], jnp.int32)
    Bp = bucket_batch_size(B)
    if Bp != B:
        pad = Bp - B
        q = jnp.concatenate([q, jnp.zeros((pad, q.shape[1]), q.dtype)])
        w = jnp.concatenate(
            [w, jnp.broadcast_to(jnp.asarray([2.0, -2.0], w.dtype), (pad, 2))])
        f = jnp.concatenate([f, jnp.full((pad,), FLAG_IF, jnp.int32)])
    return search_mixed(index.store, q, w, f, ef=ef, k=k), B


# ---------------------------------------------------------------- exactness
def test_inline_coalesced_results_match_direct_search():
    eng = make_engine()
    rt = ServeRuntime(eng)
    qv, qi, flags = make_queries(13)  # odd count: forces pad rows
    futs = [rt.submit(qv[i], qi[i], flags[i]) for i in range(13)]
    assert rt.run_until_idle() >= 1
    ids, dist = direct_rows(eng.index, qv, qi, flags)
    for i, f in enumerate(futs):
        rep = f.result(timeout=5)
        assert np.array_equal(rep.ids, ids[i])
        assert np.array_equal(rep.dist, dist[i])
        assert rep.index is eng.index
    assert rt.stats()["completed"] == 13


def test_mixed_compile_keys_split_into_exact_micro_batches():
    """Alternating (ef, k) breaks the stream into many tiny micro-batches;
    every reply must still equal the direct call on its own key."""
    eng = make_engine()
    rt = ServeRuntime(eng)
    qv, qi, flags = make_queries(12)
    keys = [(32, 5), (64, 10)]
    futs = [rt.submit(qv[i], qi[i], flags[i], ef=keys[i % 2][0],
                      k=keys[i % 2][1]) for i in range(12)]
    rt.run_until_idle()
    for (ef, k) in keys:
        sel = [i for i in range(12) if (keys[i % 2]) == (ef, k)]
        ids, dist = direct_rows(eng.index, qv, qi, flags, ef=ef, k=k, sel=sel)
        for j, i in enumerate(sel):
            rep = futs[i].result(timeout=5)
            assert rep.ids.shape == (k,)
            assert np.array_equal(rep.ids, ids[j])
            assert np.array_equal(rep.dist, dist[j])


def test_threaded_runtime_matches_direct_search():
    eng = make_engine()
    qv, qi, flags = make_queries(24)
    with ServeRuntime(eng, RuntimeConfig(max_batch=8)) as rt:
        futs = [rt.submit(qv[i], qi[i], flags[i]) for i in range(24)]
        reps = [f.result(timeout=30) for f in futs]
    ids, dist = direct_rows(eng.index, qv, qi, flags)
    for i, rep in enumerate(reps):
        assert np.array_equal(rep.ids, ids[i])
        assert np.array_equal(rep.dist, dist[i])
    s = rt.stats()
    assert s["completed"] == 24 and s["rejected"] == 0
    assert s["p99_ms"] >= s["p50_ms"] > 0


# ----------------------------------------------------- snapshot consistency
def test_no_torn_reads_across_a_write():
    """FIFO contract: queries before the remove answer the old snapshot,
    queries after answer the new one — each bitwise-equal to a direct
    search on the snapshot its reply pinned."""
    eng = make_engine()
    old_index = eng.index
    qv, qi, flags = make_queries(8)
    rt = ServeRuntime(eng)
    pre = [rt.submit(qv[i], qi[i], flags[i]) for i in range(8)]
    victim_ids = np.unique(np.concatenate(
        [direct_rows(old_index, qv, qi, flags)[0].ravel()]))
    victim_ids = victim_ids[victim_ids >= 0][:12]
    wfut = rt.submit_remove(jnp.asarray(victim_ids, jnp.int32))
    post = [rt.submit(qv[i], qi[i], flags[i]) for i in range(8)]
    rt.run_until_idle()

    assert wfut.result(timeout=5) == len(victim_ids)
    new_index = eng.index
    assert new_index is not old_index

    ids_old, dist_old = direct_rows(old_index, qv, qi, flags)
    ids_new, dist_new = direct_rows(new_index, qv, qi, flags)
    for i in range(8):
        a, b = pre[i].result(timeout=5), post[i].result(timeout=5)
        assert a.index is old_index and b.index is new_index
        assert np.array_equal(a.ids, ids_old[i])
        assert np.array_equal(a.dist, dist_old[i])
        assert np.array_equal(b.ids, ids_new[i])
        assert np.array_equal(b.dist, dist_new[i])
    # tombstoned docs never surface post-write
    gone = set(victim_ids.tolist())
    for i in range(8):
        assert not gone & set(post[i].result().ids.tolist())
    assert rt.stats()["writes"] == 1


def test_upsert_through_runtime_is_visible_to_later_queries():
    eng = make_engine(n=256)
    old_index = eng.index
    rt = ServeRuntime(eng)
    k1 = jax.random.key(99)
    xnew = jax.random.normal(k1, (16, 12))
    inew = jnp.broadcast_to(jnp.asarray([0.0, 1.0]), (16, 2))
    qv, qi, flags = make_queries(4)
    pre = [rt.submit(qv[i], qi[i], flags[i]) for i in range(4)]
    wfut = rt.submit_upsert(xnew, inew)
    post = [rt.submit(qv[i], qi[i], flags[i]) for i in range(4)]
    rt.run_until_idle()
    assert wfut.result(timeout=5) == 16
    assert eng.index is not old_index and eng.index.n == 256 + 16
    for i in range(4):
        assert pre[i].result().index is old_index
        assert post[i].result().index is eng.index


# ------------------------------------------------------ deadlines + bounds
def test_deadline_expired_at_admission_is_rejected():
    eng = make_engine()
    clk = FakeClock()
    rt = ServeRuntime(eng, clock=clk)
    qv, qi, flags = make_queries(1)
    fut = rt.submit(qv[0], qi[0], flags[0], deadline=clk() - 0.1)
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=1)
    assert rt.stats()["rejected"] == 1
    assert rt.run_until_idle() == 0  # nothing was enqueued


def test_deadline_expired_in_queue_is_rejected_not_dropped():
    eng = make_engine()
    clk = FakeClock()
    rt = ServeRuntime(eng, clock=clk)
    qv, qi, flags = make_queries(3)
    doomed = rt.submit(qv[0], qi[0], flags[0], deadline=clk() + 1.0)
    alive = [rt.submit(qv[i], qi[i], flags[i], deadline=clk() + 100.0)
             for i in (1, 2)]
    clk.advance(5.0)  # both queued; only the first expires
    rt.run_until_idle()
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=1)
    ids, dist = direct_rows(eng.index, qv, qi, flags, sel=[1, 2])
    for j, f in enumerate(alive):
        assert np.array_equal(f.result(timeout=5).ids, ids[j])
    s = rt.stats()
    assert s["rejected"] == 1 and s["completed"] == 2


def test_admission_bound_raises_queue_full():
    eng = make_engine()
    rt = ServeRuntime(eng, RuntimeConfig(max_queue=2))
    qv, qi, flags = make_queries(3)
    rt.submit(qv[0], qi[0], flags[0])
    rt.submit(qv[1], qi[1], flags[1])
    with pytest.raises(QueueFull):
        rt.submit(qv[2], qi[2], flags[2])
    rt.run_until_idle()  # the two admitted requests still complete
    assert rt.stats()["completed"] == 2


def test_runtime_requires_an_attached_index():
    with pytest.raises(ValueError):
        ServeRuntime(ServeEngine(None, None))


# -------------------------------------------------- empty batches + chunks
def test_empty_batches_never_dispatch():
    eng = make_engine()
    assert eng.remove(jnp.zeros((0,), jnp.int32)) == 0
    assert eng.upsert(None, jnp.zeros((0, 2)), x=jnp.zeros((0, 12))) == 0
    res = eng.retrieve_mixed(None, jnp.zeros((0, 2)), [], k=7,
                             q_v=jnp.zeros((0, 12)))
    assert res.ids.shape == (0, 7) and res.dist.shape == (0, 7)
    with pytest.raises(ValueError):
        bucket_batch_size(0)
    with pytest.raises(ValueError):
        bucket_batch_size(-3)


def test_upsert_chunk_plan_shapes_and_coverage():
    for n_live, total in [(300, 16), (300, 500), (64, 1000), (10_000, 3000),
                          (0, 64), (5, 1)]:
        plan = upsert_chunk_plan(n_live, total)
        assert sum(plan) == total
        top = BATCH_BUCKETS[-1]
        for i, b in enumerate(plan[:-1]):  # the tail chunk may be a remnant
            assert b in BATCH_BUCKETS or b % top == 0, (n_live, total, plan)
        # chunk i never exceeds half the live count as of chunk i (floor 64)
        live = n_live
        for b in plan:
            assert b <= max(live // 2, 64)
            live += b
    assert upsert_chunk_plan(300, 0) == []


def test_upsert_reads_liveness_exactly_once(monkeypatch):
    eng = make_engine(n=256)
    calls = {"n": 0}
    orig = UGIndex.n.fget

    def counting_n(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(UGIndex, "n", property(counting_n))
    x = jax.random.normal(jax.random.key(3), (700, 12))
    ints = jnp.broadcast_to(jnp.asarray([0.0, 1.0]), (700, 2))
    assert eng.upsert(None, ints, x=x) == 700  # multiple chunks, one sync
    assert calls["n"] == 1


def test_runtime_writer_reuses_engine_chunk_plan(monkeypatch):
    """The runtime's writer path goes through ServeEngine.upsert and so
    inherits the single-sync chunk plan."""
    eng = make_engine(n=256)
    calls = {"n": 0}
    orig = UGIndex.n.fget

    def counting_n(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(UGIndex, "n", property(counting_n))
    rt = ServeRuntime(eng)
    x = jax.random.normal(jax.random.key(4), (400, 12))
    ints = jnp.broadcast_to(jnp.asarray([0.0, 1.0]), (400, 2))
    fut = rt.submit_upsert(x, ints)
    rt.run_until_idle()
    assert fut.result(timeout=5) == 400
    assert calls["n"] == 1


# ------------------------------------------------------- stats (ISSUE-7)
def test_pctl_nearest_rank_known_quantiles():
    """Nearest-rank percentile: index ceil(q*n)-1.  The old int(q*n) sat
    one rank high — the median of [1, 2] came back as 2."""
    from repro.serve.runtime import _pctl

    assert _pctl([], 0.5) == 0.0
    assert _pctl([7.0], 0.5) == 7.0
    assert _pctl([1.0, 2.0], 0.5) == 1.0          # the ISSUE-7 repro
    xs = [1.0, 2.0, 3.0, 4.0]
    assert _pctl(xs, 0.25) == 1.0
    assert _pctl(xs, 0.50) == 2.0
    assert _pctl(xs, 0.75) == 3.0
    assert _pctl(xs, 0.99) == 4.0
    assert _pctl(xs, 1.00) == 4.0
    hundred = [float(i) for i in range(1, 101)]
    assert _pctl(hundred, 0.50) == 50.0
    assert _pctl(hundred, 0.99) == 99.0
    assert _pctl(hundred, 0.999) == 100.0


def test_latency_reservoir_bounds_memory_and_samples_uniformly():
    from repro.serve.runtime import LatencyReservoir

    r = LatencyReservoir(100, seed=0)
    for i in range(10_000):
        r.offer(float(i))
    assert len(r) == 100 and r.seen == 10_000
    vals = sorted(r)
    assert all(0.0 <= v < 10_000 for v in vals)
    # a uniform sample of 0..9999 lands a near-uniform spread, not the head
    assert vals[0] < 2_000 and vals[-1] > 8_000
    # seeded: two identical streams hold identical samples
    r2 = LatencyReservoir(100, seed=0)
    r2.extend(float(i) for i in range(10_000))
    assert sorted(r2) == vals
    # below cap: verbatim
    r3 = LatencyReservoir(100)
    r3.extend([3.0, 1.0, 2.0])
    assert sorted(r3) == [1.0, 2.0, 3.0]
    with pytest.raises(ValueError):
        LatencyReservoir(0)


def test_runtime_latencies_are_bounded():
    rt = ServeRuntime(make_engine())
    from repro.serve.runtime import LatencyReservoir

    assert isinstance(rt._latencies, LatencyReservoir)


def test_stats_wall_clock_covers_active_windows_only():
    """ISSUE-7 satellite: qps must be measured over start/stop windows (and
    run_until_idle pumps), not since construction — idle time between
    cycles and pre-start build time must not dilute it."""
    eng = make_engine()
    clk = FakeClock()
    qv, qi, flags = make_queries(8)
    # warm the search_mixed compile cache on a throwaway runtime so the
    # timed cycles below never sit behind a cold XLA compile
    warm = ServeRuntime(eng, RuntimeConfig(max_batch=8))
    for i in range(8):
        warm.submit(qv[i], qi[i], flags[i])
    warm.run_until_idle()

    rt = ServeRuntime(eng, RuntimeConfig(max_batch=8), clock=clk)
    clk.advance(500.0)                 # idle before serving ever starts
    rt.start()
    futs = [rt.submit(qv[i], qi[i], flags[i]) for i in range(8)]
    for f in futs:
        f.result(timeout=120)
    clk.advance(2.0)                   # the only active wall time
    rt.stop()
    clk.advance(500.0)                 # idle after stop
    s = rt.stats()
    assert s["completed"] == 8
    assert s["qps"] == pytest.approx(8 / 2.0)

    # a second start/stop cycle extends the window, idle gaps still excluded
    rt.start()
    futs = [rt.submit(qv[i], qi[i], flags[i]) for i in range(8)]
    for f in futs:
        f.result(timeout=120)
    clk.advance(3.0)
    rt.stop()
    s = rt.stats()
    assert s["completed"] == 16
    assert s["qps"] == pytest.approx(16 / 5.0)


def test_stats_wall_clock_inline_mode():
    """Inline pumps count their own wall time; construction-to-run idle
    time does not leak into the qps denominator (the old behaviour made
    run_until_idle users report near-zero qps)."""
    eng = make_engine()
    clk = FakeClock()
    rt = ServeRuntime(eng, clock=clk)
    qv, qi, flags = make_queries(5)
    clk.advance(1000.0)                # idle: would dominate the old window
    for i in range(5):
        rt.submit(qv[i], qi[i], flags[i])
    rt.run_until_idle()
    s = rt.stats()
    assert s["completed"] == 5
    # the fake clock does not tick inside the pump, so the active window is
    # ~0 — any qps below completed/1s means idle time leaked in
    assert s["qps"] > 5.0


# -------------------------------------------------------------------- spans
def trace_spans(trace_dir):
    """The program's ``serve.*``/``search.*`` host spans in the newest trace
    under ``trace_dir``: ``(name, thread, start_ns, end_ns, stats)``, where
    ``thread`` tells the trace's lines apart."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("serve.", "search.")):
                    out.append((ev.name, (plane.name, li), ev.start_ns,
                                ev.end_ns, dict(ev.stats)))
    return out


def test_traced_runtime_spans_each_batch(tmp_path):
    """Under a profiler trace, each batch carries pack, launch and resolve
    spans with one ``batch`` number, the search's spans nest inside its
    launch, the dispatcher's spans tile its loop, and the resolve span's
    counts equal a direct ``search_mixed`` of the batch's rows."""
    eng = make_engine()
    qv, qi, flags = make_queries(20)
    rt = ServeRuntime(eng, RuntimeConfig(max_batch=8))
    # Queued before the threads start, so the batches are rows 0-7, 8-15
    # and 16-19 (padded to 8).
    futs = [rt.submit(qv[i], qi[i], flags[i]) for i in range(20)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rt:
            for f in futs:
                f.result(timeout=60)
    finally:
        jax.profiler.stop_trace()
    spans = trace_spans(str(tmp_path))

    def of(name):
        return sorted((s for s in spans if s[0] == name), key=lambda s: s[2])

    packs, launches, resolves = of("serve.pack"), of("serve.launch"), of("serve.resolve")
    assert [s[4]["batch"] for s in packs] == [0, 1, 2]
    assert [s[4]["batch"] for s in launches] == [0, 1, 2]
    assert [s[4]["batch"] for s in resolves] == [0, 1, 2]
    assert [s[4]["batch"] for s in of("serve.device_wait")] == [0, 1, 2]
    assert [(s[4]["live"], s[4]["bucket"]) for s in packs] == [(8, 8), (8, 8), (4, 8)]
    for s in packs:
        assert s[4]["queue_wait_ms_max"] >= s[4]["queue_wait_ms_med"] >= 0
        assert s[4]["admit_s"] >= 0 and s[4]["compiles"] >= 0

    # search.* spans nest inside their launch, on the launch's thread.
    for child in ("search.flags", "search.entry", "search.beam"):
        kids = of(child)
        assert len(kids) == 3
        for kid, launch in zip(kids, launches):
            assert kid[1] == launch[1]
            assert launch[2] <= kid[2] <= kid[3] <= launch[3]

    # The dispatcher's top-level spans follow one another without overlap.
    thread = packs[0][1]
    top = sorted((s for s in spans if s[1] == thread and s[0].startswith("serve.")),
                 key=lambda s: s[2])
    assert {s[0] for s in top} >= {"serve.dequeue", "serve.pack", "serve.launch",
                                   "serve.inflight_put"}
    assert all(a[3] <= b[2] for a, b in zip(top, top[1:]))

    for s, rows in zip(resolves, ([*range(8)], [*range(8, 16)], [*range(16, 20)])):
        res, B = direct_search(eng.index, qv, qi, flags, sel=rows)
        steps = np.asarray(res.steps)[:B]
        st = s[4]
        assert (st["live"], st["bucket"], st["width"]) == (B, res.ids.shape[0], 4)
        assert st["iters"] == int(res.iters)
        assert st["steps_sum"] == int(steps.sum())
        assert st["steps_max"] == int(steps.max())
        assert 0 < st["steps_sum"] <= st["iters"] * st["width"] * st["bucket"]


def test_compile_counter_counts_new_programs():
    from repro.trace import compiles, count_compiles

    count_compiles()
    count_compiles()            # registered once: one compile counts once
    x = jnp.ones((3, 7)).block_until_ready()
    c0 = compiles()
    jax.jit(lambda x: x * 3.25 + 1.5)(x).block_until_ready()
    assert compiles() == c0 + 1
