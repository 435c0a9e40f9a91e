#!/usr/bin/env python3
"""Smoke run of the interval-aware vector index on TPU chips.

One chip (the default)::

    python chip_smoke.py [--seed S] [--n ROWS]

generates a DEEP1M-shaped corpus from ``--seed`` (96-d f32 rows with uniform
validity intervals; ``N_ROWS`` of them are indexed, the next 1,024 are the
query vectors and the last 4,096 the rows upserted later, all from one
``make_corpus`` call), builds a UG index through ``UGIndex.build``, attaches
it to a ``ServeEngine`` and sends 1,024 single-query requests (IF, IS, RF
and RS, 1:1:1:1, windows from ``make_queries``) through ``ServeRuntime``.
The same requests then run on the int8 and pq planes of the same graph, and
through the XLA backend on the f32 plane.  Last, 4,096 upserts and 4,096
deletes go through the runtime and the requests run again.

Checks (any failure exits non-zero): every future resolves; recall@10 per
semantics and plane against an exact (``HIGHEST``-precision) brute-force
oracle; Pallas/XLA agreement; no deleted id returned; each upserted row is
the oracle's nearest live row for its own vector; and the compiled search
and prune programs contain Mosaic kernels (``tpu_custom_call``).

Four chips::

    python chip_smoke.py --chips 4

runs only the sharded path: ``build_sharded_store`` over 250,000 rows per
chip on a 4-device mesh, mixed-semantics batches through
``make_sharded_search_fn``, recall against brute force over the whole
corpus, and a check that each shard of the plane and the graph sits on its
own device.

Every figure printed before the last line is a smoke figure, not a
benchmark metric.  The last line is one JSON object naming the device.
The script exits non-zero, printing no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import re
import sys
import threading
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
RECALL_FLOOR = 0.90      # recall@10 per semantics and plane, at EF
AGREE_FLOOR = 0.99       # share of the Pallas ids the XLA backend returns too
EF, EF_SMALL, K, WIDTH = 512, 128, 10, 4
DIM = 96
DEEP1M_ROWS = 1_000_000
# n is cut from DEEP1M's 1,000,000 for time: on one v5e the exact KNN scan
# took 38.4 s at 250,000 rows and grows with n², so about 615 s at 1,000,000,
# and a whole cold run at 250,000 took 504 s; at 500,000 the run fits 900 s.
N_ROWS = DEEP1M_ROWS // 2
ROWS_PER_CHIP_4 = 250_000
N_QUERIES = 1024
N_WRITES = 4096
WRITE_CHUNK = 1024
BATCH = 256              # ServeRuntime micro-batch cap
# Exact KNN spatial candidates (NN-descent finds about 30% of the true
# nearest neighbours at 100,000 rows of this data), 64 + 64 edges.
BUILD = dict(exact_spatial=True, max_edges_if=64, max_edges_is=64,
             ef_spatial=64, ef_attribute=64, iterations=3, repair_width=16)
PQ_M = 48                # 2 dims per subspace
MOSAIC_KERNELS = ("expand_score", "expand_score_q", "expand_score_pq",
                  "beam_merge", "prune_sweep")
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Smoke:
    """Phase timer (wall and compile seconds) and the list of failed checks."""

    def __init__(self):
        import jax

        self.failures: list[str] = []
        self._compile_s = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self._compile_s += duration

    @contextlib.contextmanager
    def phase(self, name):
        c0, t0 = self._compile_s, time.perf_counter()
        yield
        print(f"[phase] {name}: {time.perf_counter() - t0:.3f} s wall, "
              f"{self._compile_s - c0:.3f} s tracing+compiling (smoke figure)",
              flush=True)

    def check(self, ok, what):
        print(f"[check] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)


def mosaic_kernels(compiled_text: str) -> set[str]:
    """Names of the Mosaic kernels (``tpu_custom_call`` instructions) in a
    compiled program's text."""
    return set(re.findall(r"%([A-Za-z_]\w*?)(?:\.\d+)* = [^\n]*"
                          r'custom_call_target="tpu_custom_call"', compiled_text))


# ------------------------------------------------------------------ phases
def make_data(n, seed, n_queries=N_QUERIES, n_writes=N_WRITES):
    """One ``make_corpus`` call over ``n + n_queries + n_writes`` rows: the
    first ``n`` are indexed, the next ``n_queries`` are query vectors (their
    windows from ``make_queries``: uniform, and point windows for RS), the
    last ``n_writes`` are upserted later.  Returns a dict of arrays."""
    from repro.core import Semantics
    from repro.data.synthetic import CorpusConfig, make_corpus, make_queries

    total = CorpusConfig(n=n + n_queries + n_writes, dim=DIM, seed=seed)
    x, ivs = make_corpus(total)
    cfg = CorpusConfig(n=n, dim=DIM, seed=seed)
    _, q_uni = make_queries(cfg, n_queries, workload="uniform", seed=seed + 100)
    _, q_pt = make_queries(cfg, n_queries, workload="point", seed=seed + 100)
    cycle = (Semantics.IF, Semantics.IS, Semantics.RF, Semantics.RS)
    sems = [cycle[i % 4] for i in range(n_queries)]
    is_rs = np.array([s is Semantics.RS for s in sems])
    return dict(
        x=x[:n], ivs=ivs[:n],
        qv=np.asarray(x[n:n + n_queries]),
        qint=np.where(is_rs[:, None], np.asarray(q_pt), np.asarray(q_uni)),
        sems=sems,
        new_x=np.asarray(x[n + n_queries:]), new_iv=np.asarray(ivs[n + n_queries:]),
    )


def recall_by_semantics(ids, x, intervals, qv, qint, sems, alive=None):
    """recall@K per semantics against the exact brute-force oracle."""
    from repro.core import Semantics, brute_force, recall
    from repro.core.search import SearchResult

    out = {}
    for sem in (Semantics.IF, Semantics.IS, Semantics.RF, Semantics.RS):
        rows = np.array([i for i, s in enumerate(sems) if s is sem])
        if rows.size == 0:
            continue
        truth = brute_force(x, intervals, qv[rows], qint[rows], sem=sem, k=K,
                            block=65536, alive=alive)
        out[sem.value] = recall(SearchResult(ids[rows], None, None), truth)
    return out


def agreement(ids_a, ids_b) -> float:
    """Share of the ids in ``ids_a`` that ``ids_b`` returns for the same query."""
    hit = tot = 0
    for a, b in zip(ids_a, ids_b):
        sa = {int(v) for v in a if v >= 0}
        hit += len(sa & {int(v) for v in b if v >= 0})
        tot += len(sa)
    return hit / max(tot, 1)


def serve(smoke, index, qv, qint, sems, *, backend=None, ef=EF, writes=()):
    """Submit ``writes`` (callables taking the runtime), then one request per
    query, to a ``ServeRuntime`` over a ``ServeEngine`` holding ``index``.
    Returns the ``(nq, K)`` ids and the engine."""
    from repro.serve.engine import ServeEngine
    from repro.serve.runtime import RuntimeConfig, ServeRuntime

    engine = ServeEngine(model=None, params=None)
    engine.attach_index(index, backend=backend, width=WIDTH)
    rt = ServeRuntime(engine, RuntimeConfig(
        max_batch=BATCH, max_queue=len(qv) + len(writes) + 16,
        default_ef=ef, default_k=K))
    with rt:
        futs = [submit(rt) for submit in writes]
        futs += [rt.submit(qv[i], qint[i], sems[i], ef=ef, k=K)
                 for i in range(len(qv))]
        errors = [f.exception() for f in futs]
    errors = [e for e in errors if e is not None]
    for e in errors[:5]:
        print(f"[error] {e!r}", flush=True)
    smoke.check(not errors, f"all {len(futs)} futures resolved without an "
                            f"exception ({len(errors)} raised)")
    if errors:
        raise RuntimeError("serving failed")
    ids = np.stack([f.result().ids for f in futs[len(writes):]])
    return ids, engine


def build(smoke, data, seed, config=BUILD):
    from repro.core import UGConfig, UGIndex

    ucfg = UGConfig(**config)
    print(f"[build] {ucfg}", flush=True)
    with smoke.phase(f"build: UGIndex.build over {data['x'].shape[0]:,} rows"):
        idx = UGIndex.build(data["x"], data["ivs"], ucfg, seed=seed)
    print(f"[build] degree {idx.degree_stats()}", flush=True)
    return idx


def serve_planes(smoke, idx, data, *, backend=None, pq_m=PQ_M):
    """The requests on the f32, int8 and pq planes of one graph, each at
    ``EF`` through the runtime; recall gated per semantics.  Returns the
    f32 ids and the three indexes."""
    d = data
    planes = {"f32": idx, "int8": idx.with_dtype("int8"),
              "pq": idx.with_dtype("pq", pq_m=pq_m)}
    answers = {}
    for plane, index in planes.items():
        with smoke.phase(f"serve {plane}: {len(d['qv'])} requests at ef={EF}"):
            answers[plane], _ = serve(smoke, index, d["qv"], d["qint"],
                                      d["sems"], backend=backend)
        rec = recall_by_semantics(answers[plane], d["x"], d["ivs"], d["qv"],
                                  d["qint"], d["sems"])
        for sem, r in rec.items():
            smoke.check(r >= RECALL_FLOOR,
                        f"{plane} {sem} recall@{K} {r:.4f} >= {RECALL_FLOOR}")
    return answers["f32"], planes


def compare_xla(smoke, idx, data, ids_pallas):
    d = data
    with smoke.phase(f"serve f32 backend=xla: {len(d['qv'])} requests at ef={EF}"):
        ids_xla, _ = serve(smoke, idx, d["qv"], d["qint"], d["sems"], backend="xla")
    agree = agreement(ids_pallas, ids_xla)
    smoke.check(agree >= AGREE_FLOOR,
                f"pallas/xla agreement {agree:.4f} of returned ids >= {AGREE_FLOOR}")


def small_ef(smoke, idx, data, *, backend=None):
    d = data
    with smoke.phase(f"serve f32: {len(d['qv'])} requests at ef={EF_SMALL}"):
        ids, _ = serve(smoke, idx, d["qv"], d["qint"], d["sems"],
                       backend=backend, ef=EF_SMALL)
    rec = recall_by_semantics(ids, d["x"], d["ivs"], d["qv"], d["qint"], d["sems"])
    print(f"[info] f32 recall@{K} at ef={EF_SMALL} (not gated): "
          + " ".join(f"{k}={v:.4f}" for k, v in rec.items()), flush=True)


def writes(smoke, idx, data, seed, *, backend=None, chunk=WRITE_CHUNK):
    """Upserts of the held-out rows and deletes of as many built rows, in
    ``chunk``-row writes through the runtime, then the requests again."""
    import jax.numpy as jnp

    from repro.core import Semantics, brute_force

    d = data
    n, n_new = d["x"].shape[0], d["new_x"].shape[0]
    dead = np.random.default_rng(seed).choice(n, n_new, replace=False).astype(np.int32)
    ops = [functools.partial(lambda rt, s: rt.submit_upsert(
               d["new_x"][s:s + chunk], d["new_iv"][s:s + chunk]), s=s)
           for s in range(0, n_new, chunk)]
    ops += [functools.partial(lambda rt, s: rt.submit_remove(dead[s:s + chunk]), s=s)
            for s in range(0, n_new, chunk)]
    with smoke.phase(f"writes: {n_new} upserts + {n_new} deletes, then "
                     f"{len(d['qv'])} requests"):
        ids, engine = serve(smoke, idx, d["qv"], d["qint"], d["sems"],
                            backend=backend, writes=ops)
    smoke.check(not np.isin(ids, dead).any(),
                "no deleted id is returned after the deletes")
    after = engine.index
    # Each upserted vector's nearest live row, by the oracle over the served
    # snapshot (window [0, 1] under IF holds every row), holds that vector.
    whole = np.tile(np.asarray([[0.0, 1.0]], np.float32), (n_new, 1))
    truth = brute_force(after.x, after.intervals, d["new_x"], whole,
                        sem=Semantics.IF, k=1, block=65536, alive=after.alive)
    top = np.asarray(truth.ids[:, 0])
    same = (top >= 0) & np.all(
        np.asarray(after.x[jnp.asarray(np.maximum(top, 0))]) == d["new_x"], axis=1)
    smoke.check(bool(same.all()),
                f"each upserted row is the oracle's nearest live row for its "
                f"own vector ({int(same.sum())}/{n_new})")
    rec = recall_by_semantics(ids, after.x, after.intervals, d["qv"], d["qint"],
                              d["sems"], alive=after.alive)
    print("[info] recall@10 after the writes (not gated): "
          + " ".join(f"{k}={v:.4f}" for k, v in rec.items()), flush=True)


def mosaic_check(smoke, idx, planes, data):
    """Compile the search program the runtime runs (one per plane, at the
    ``BATCH`` bucket) and the build's prune program, and look for Mosaic
    kernels in the compiled text: that is how the run shows it ran as
    Mosaic.  The programs are the ones already compiled, so with the
    persistent compilation cache on these are cache hits."""
    import jax
    import jax.numpy as jnp

    from repro.core import as_sem_flags
    from repro.core.build import _prune_all
    from repro.core.candidates import candidate_pool_width
    from repro.core.entry import get_entry_batch_flags
    from repro.core.search import _beam_search_flags_impl

    qv = jnp.asarray(data["qv"][:BATCH])
    qint = jnp.asarray(data["qint"][:BATCH])
    flags = as_sem_flags(data["sems"][:BATCH], BATCH)
    seen = set()
    with smoke.phase("mosaic check: compile search and prune programs"):
        for plane, index in planes.items():
            st = index.store
            entry = get_entry_batch_flags(st.entry, qint, flags, width=WIDTH)
            text = _beam_search_flags_impl.lower(
                st.plane, st.rerank, st.intervals, st.nbrs, st.status, st.alive,
                entry, qv, qint, flags, ef=EF, k=K, max_steps=0, backend=None,
                width=WIDTH).compile().as_text()
            found = mosaic_kernels(text)
            smoke.check(bool(found), f"compiled {plane} search program contains "
                                     f"tpu_custom_call ({', '.join(sorted(found))})")
            seen |= found
        cfg = idx.config
        pool = candidate_pool_width(cfg.ef_spatial, cfg.ef_attribute)
        cand = jax.ShapeDtypeStruct((idx.x.shape[0], pool), jnp.int32)
        keep = min(cfg.max_edges_if + cfg.max_edges_is, pool)
        text = _prune_all.lower(idx.x, idx.intervals, cand, cfg, keep,
                                cfg.prune_backend).compile().as_text()
        found = mosaic_kernels(text)
        smoke.check("prune_sweep" in found, "compiled build prune program "
                    f"contains tpu_custom_call ({', '.join(sorted(found))})")
        seen |= found
    print("[kernels] " + " ".join(
        f"{k}={'pallas' if k in seen else 'not-found'}" for k in MOSAIC_KERNELS),
        flush=True)


def one_chip(smoke, seed, n):
    import jax

    if n < DEEP1M_ROWS:
        print(f"[cut] n={n:,} indexed rows (the DEEP1M shape is {DEEP1M_ROWS:,})")
    with smoke.phase(f"data: make_corpus {n + N_QUERIES + N_WRITES:,} x {DIM} f32"):
        data = make_data(n, seed)
        jax.block_until_ready((data["x"], data["ivs"]))
    idx = build(smoke, data, seed)
    ids_f32, planes = serve_planes(smoke, idx, data)
    compare_xla(smoke, idx, data, ids_f32)
    small_ef(smoke, idx, data)
    mosaic_check(smoke, idx, planes, data)
    writes(smoke, idx, data, seed)


def four_chips(smoke, seed, n_per_chip):
    import jax
    from jax.sharding import Mesh

    from repro.core import UGConfig, as_sem_flags
    from repro.core.sharded import build_sharded_store, make_sharded_search_fn

    devs = jax.devices()
    smoke.check(len(devs) == 4, f"four devices present ({len(devs)})")
    if len(devs) != 4:
        return len(devs)
    mesh = Mesh(np.array(devs), ("data",))
    n = 4 * n_per_chip
    if n_per_chip < DEEP1M_ROWS:
        print(f"[cut] {n:,} rows, {n_per_chip:,} per chip "
              f"(the DEEP1M shape is {DEEP1M_ROWS:,} per chip)")
    with smoke.phase(f"data: make_corpus {n + N_QUERIES:,} x {DIM} f32"):
        data = make_data(n, seed, n_writes=0)
        x, ivs = np.asarray(data["x"]), np.asarray(data["ivs"])
    ucfg = UGConfig(**BUILD)
    print(f"[build] {ucfg}", flush=True)
    with smoke.phase("build: build_sharded_store on 4 chips"):
        sidx = build_sharded_store(mesh, x, ivs, ucfg, index_axes=("data",))
        jax.block_until_ready(sidx)
    st = sidx.store
    for name, arr in (("plane", st.plane.data), ("intervals", st.intervals),
                      ("nbrs", st.nbrs), ("status", st.status),
                      ("global_ids", sidx.global_ids)):
        shards = arr.addressable_shards
        ids = [s.device.id for s in shards]
        print(f"[shards] {name}: devices {ids}, rows per shard "
              f"{[s.data.shape[0] for s in shards]}")
        smoke.check(len(set(ids)) == 4 == len(shards),
                    f"{name}: one shard on each of 4 distinct devices")

    qv, qint, sems = data["qv"], data["qint"], data["sems"]
    flags = np.asarray(as_sem_flags(sems, len(qv)))
    fn = make_sharded_search_fn(mesh, index_axes=("data",), ef=EF, k=K,
                                width=WIDTH, mixed=True)
    with smoke.phase(f"search: {len(qv)} mixed queries, batches of {BATCH}"):
        ids = np.concatenate([
            np.asarray(fn(sidx, qv[s:s + BATCH], qint[s:s + BATCH],
                          flags[s:s + BATCH])[0])
            for s in range(0, len(qv), BATCH)])
    found = mosaic_kernels(fn.lower(
        sidx, qv[:BATCH], qint[:BATCH], flags[:BATCH]).compile().as_text())
    smoke.check(bool(found), "compiled sharded search program contains "
                f"tpu_custom_call ({', '.join(sorted(found))})")
    for sem, r in recall_by_semantics(ids, x, ivs, qv, qint, sems).items():
        smoke.check(r >= RECALL_FLOOR,
                    f"sharded {sem} recall@{K} {r:.4f} >= {RECALL_FLOOR}")
    return 4


def main(argv=None) -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {dev.platform!r}", file=sys.stderr)
        return 2
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded path on four chips, and nothing else")
    ap.add_argument("--n", type=int, default=None,
                    help=f"indexed rows (default {N_ROWS:,} on one chip, "
                         f"{ROWS_PER_CHIP_4:,} per chip on four)")
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: the repository's sources are not beside "
              f"{pathlib.Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[setup] compile cache {enable_compile_cache()}")
    print(f"[setup] device_kind {dev.device_kind}, {len(jax.devices())} "
          f"device(s), jax {jax.__version__}", flush=True)
    smoke = Smoke()
    t0 = time.perf_counter()
    if args.chips == 4:
        count = four_chips(smoke, args.seed, args.n or ROWS_PER_CHIP_4)
    else:
        one_chip(smoke, args.seed, args.n or N_ROWS)
        count = 1
    for d in jax.devices()[:count]:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"[memory] device {d.id} peak_bytes_in_use {peak} (smoke figure)")
    print(f"[setup] total {time.perf_counter() - t0:.3f} s wall (smoke figure)")
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed: "
              + "; ".join(smoke.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
