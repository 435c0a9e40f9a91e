"""End-to-end interval-aware retrieval serving (the paper's deployment).

Pipeline: LM tower embeds a synthetic document corpus → UG unified index is
built over (embedding, validity-interval) pairs → batched queries run under
all four semantics (IFANN / ISANN / RFANN / RSANN) against brute-force truth.

Example::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --reduced \
        --docs 2000 --queries 64
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.core import Semantics, UGConfig, UGIndex, recall
from repro.core import intervals as iv
from repro.models.api import get_model
from repro.serve import ServeEngine
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen1.5-4b")
    # store_true + default=True made --reduced a no-op (the full-size config
    # was unreachable); BooleanOptionalAction restores --no-reduced.
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True, help="use the reduced config (--no-reduced "
                    "serves the full-size architecture)")
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--doc-len", type=int, default=32)
    ap.add_argument("--ef", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--backend", default=None,
                    choices=["pallas", "xla", "legacy"],
                    help="search pipeline (default: fused; pallas on TPU, xla on CPU)")
    ap.add_argument("--width", type=int, default=4,
                    help="fused multi-expansion frontier width W")
    ap.add_argument("--dtype", default="f32",
                    choices=["f32", "bf16", "int8", "pq"],
                    help="vector scan plane of the served index (int8/pq "
                         "auto-attach the f32 rerank plane; DESIGN.md §12/§14)")
    ap.add_argument("--mixed", action="store_true",
                    help="also serve one interleaved IF/IS/RF/RS stream "
                         "through the runtime-semantics path and compare "
                         "against four per-semantics batches")
    ap.add_argument("--dynamic", action="store_true",
                    help="churn demo: delete 10%% of the corpus and upsert "
                         "replacement docs through the streaming update "
                         "subsystem (DESIGN.md §11), then re-evaluate recall")
    ap.add_argument("--async", dest="async_serve", action="store_true",
                    help="async continuous-batching demo: stream the mixed "
                         "workload through ServeRuntime with per-request "
                         "deadlines and concurrent churn writes, printing "
                         "sustained QPS and p50/p99 (DESIGN.md §13)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    spec = get_arch(args.arch)
    cfg = spec.reduced if args.reduced else spec.config
    if cfg.family == "encdec":
        print("[serve] encdec tower: using decoder-only embedding of tokens")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params)

    # 1) embed the corpus with the LM tower
    key = jax.random.key(1)
    k_doc, k_iv, k_q = jax.random.split(key, 3)
    doc_tokens = jax.random.randint(k_doc, (args.docs, args.doc_len), 0, cfg.vocab)
    t0 = time.perf_counter()
    embs = []
    bs = 256
    for s in range(0, args.docs, bs):
        embs.append(engine.embed(doc_tokens[s : s + bs]))
    x = jnp.concatenate(embs)
    print(f"[serve] embedded {args.docs} docs (d={x.shape[1]}) "
          f"in {time.perf_counter() - t0:.1f}s")

    # 2) validity intervals (uniform interval model §3.2) + unified index
    intervals = iv.sample_uniform_intervals(k_iv, args.docs)
    ucfg = UGConfig(ef_spatial=32, ef_attribute=64, max_edges_if=32,
                    max_edges_is=32, iterations=3, repair_width=16,
                    exact_spatial=args.docs <= 4096)
    idx = UGIndex.build(x, intervals, ucfg, dtype=args.dtype)
    engine.attach_index(idx, backend=args.backend, width=args.width)
    vm = idx.vector_memory_bytes()
    print(f"[serve] UG built in {idx.build_seconds:.1f}s "
          f"({args.dtype} plane, {vm['plane_bytes_per_vector']:.1f} B/vec) "
          f"degree stats {idx.degree_stats()}")

    # 3) queries under all four semantics (one index!)
    q_tokens = jax.random.randint(k_q, (args.queries, args.doc_len), 0, cfg.vocab)
    qv = engine.embed(q_tokens)
    c = jax.random.uniform(jax.random.fold_in(k_q, 1), (args.queries, 1))
    wide = jnp.concatenate(
        [jnp.maximum(c - 0.3, 0.0), jnp.minimum(c + 0.3, 1.0)], axis=1
    )
    point = jnp.concatenate([c, c], axis=1)

    for sem, qint in [
        (Semantics.IF, wide), (Semantics.IS, wide),
        (Semantics.RS, point), (Semantics.RF, wide),
    ]:
        t0 = time.perf_counter()
        # qv was embedded once above; timing stays search-only and comparable
        # across semantics (the embed cost is semantics-independent).
        res = engine.retrieve(None, qint, sem=sem, ef=args.ef, k=args.k, q_v=qv)
        jax.block_until_ready(res.ids)
        dt = time.perf_counter() - t0
        gt = idx.ground_truth(qv, qint, sem=sem, k=args.k)
        r = recall(res, gt)
        qps = args.queries / dt
        print(f"[serve] {sem.value}: recall@{args.k} {r:.3f}  "
              f"QPS {qps:,.0f}  mean hops {float(res.steps.mean()):.1f}")

    # 4) mixed workload: every request carries its own semantics; one
    #    compiled program serves the interleaved stream (DESIGN.md §10)
    if args.mixed:
        cycle = [Semantics.IF, Semantics.IS, Semantics.RS, Semantics.RF]
        sems = [cycle[i % 4] for i in range(args.queries)]
        is_rs = jnp.asarray([s is Semantics.RS for s in sems])
        qmix = jnp.where(is_rs[:, None], point, wide)

        def run_mixed():
            return engine.retrieve_mixed(None, qmix, sems, ef=args.ef,
                                         k=args.k, q_v=qv)

        res = run_mixed()  # warmup/compile
        t0 = time.perf_counter()
        res = run_mixed()
        jax.block_until_ready(res.ids)
        dt_mixed = time.perf_counter() - t0

        subsets = {s: [i for i, ss in enumerate(sems) if ss is s] for s in cycle}

        # keyed by sem value: enum keys are not sortable as a jax pytree
        def run_split():
            return {s.value: engine.retrieve(None, qmix[jnp.asarray(sel)],
                                             sem=s, ef=args.ef, k=args.k,
                                             q_v=qv[jnp.asarray(sel)])
                    for s, sel in subsets.items()}

        outs = run_split()  # warmup/compile
        t0 = time.perf_counter()
        outs = run_split()
        jax.block_until_ready(outs)  # all four batches, not just the last
        dt_split = time.perf_counter() - t0

        recs = []
        for s, sel in subsets.items():
            sel = jnp.asarray(sel)
            gt = idx.ground_truth(qv[sel], qmix[sel], sem=s, k=args.k)
            part = type(res)(res.ids[sel], res.dist[sel], res.steps[sel])
            recs.append(f"{s.value}={recall(part, gt):.3f}")
        # batch-synchronous iteration counts: the hardware-independent QPS
        # signal (CPU wall-clock is B-linear per iteration; DESIGN.md §10)
        it_mixed = int(res.iters)
        it_split = sum(int(outs[s.value].iters) for s in cycle)
        print(f"[serve] mixed 4-semantics stream: QPS {args.queries/dt_mixed:,.0f} "
              f"vs split-by-semantics QPS {args.queries/dt_split:,.0f} "
              f"({dt_split/dt_mixed:.2f}x wall)  sync iters {it_mixed} vs "
              f"{it_split} ({it_split/max(it_mixed, 1):.2f}x)  "
              f"recall@{args.k} {' '.join(recs)}")

    # 5) dynamic churn: the streaming update subsystem (DESIGN.md §11) —
    #    tombstone deletes + iterative repair, then bucketed upserts; the
    #    same index keeps serving all four semantics without a rebuild
    if args.dynamic:
        import numpy as np

        n_churn = max(args.docs // 10, 1)
        rng = np.random.default_rng(5)
        dead = jnp.asarray(
            rng.choice(args.docs, size=n_churn, replace=False).astype(np.int32)
        )
        t0 = time.perf_counter()
        engine.remove(dead)
        jax.block_until_ready(engine.index.graph.nbrs)
        dt_del = time.perf_counter() - t0
        new_tokens = jax.random.randint(
            jax.random.fold_in(k_doc, 9), (n_churn, args.doc_len), 0, cfg.vocab
        )
        new_iv = iv.sample_uniform_intervals(jax.random.fold_in(k_iv, 9), n_churn)
        t0 = time.perf_counter()
        engine.upsert(new_tokens, new_iv)
        jax.block_until_ready(engine.index.graph.nbrs)
        dt_ins = time.perf_counter() - t0
        idx2 = engine.index
        print(f"[serve] dynamic churn: {n_churn} deletes in {dt_del:.1f}s "
              f"({n_churn/dt_del:,.0f}/s), {n_churn} upserts in {dt_ins:.1f}s "
              f"({n_churn/dt_ins:,.0f}/s); {idx2.n} live of "
              f"{idx2.capacity} slots")
        for sem, qint in [(Semantics.IF, wide), (Semantics.IS, wide)]:
            res = engine.retrieve(None, qint, sem=sem, ef=args.ef, k=args.k,
                                  q_v=qv)
            gt = idx2.ground_truth(qv, qint, sem=sem, k=args.k)
            print(f"[serve] {sem.value} after churn: "
                  f"recall@{args.k} {recall(res, gt):.3f}")

    # 6) async serving: the continuous-batching runtime (DESIGN.md §13) —
    #    requests trickle in one at a time with their own semantics + a
    #    deadline, writes churn the corpus mid-stream, and the coalescer
    #    re-packs everything into bucket-shaped micro-batches for the same
    #    compiled programs the batched path uses
    if args.async_serve:
        from repro.serve import RuntimeConfig, ServeRuntime

        cycle = [Semantics.IF, Semantics.IS, Semantics.RS, Semantics.RF]
        sems = [cycle[i % 4] for i in range(args.queries)]
        is_rs = jnp.asarray([s is Semantics.RS for s in sems])
        qmix = jnp.where(is_rs[:, None], point, wide)
        n_churn = max(args.docs // 20, 1)
        new_x = engine.embed(jax.random.randint(
            jax.random.fold_in(k_doc, 11), (n_churn, args.doc_len), 0,
            cfg.vocab))
        new_iv = iv.sample_uniform_intervals(jax.random.fold_in(k_iv, 11),
                                             n_churn)
        # warm the bucket programs so the measured stream is compile-free
        engine.retrieve_mixed(None, qmix[:1], sems[:1], ef=args.ef,
                              k=args.k, q_v=qv[:1])
        with ServeRuntime(engine, RuntimeConfig(max_batch=64)) as rt:
            futs = []
            wfut = None
            for i in range(args.queries):
                # generous deadline: the first mid-stream upsert pays one-off
                # jit compiles that dwarf steady-state service time
                futs.append(rt.submit(
                    qv[i], qmix[i], sems[i], ef=args.ef, k=args.k,
                    deadline=rt.clock() + 600.0))
                if i == args.queries // 2:  # churn mid-stream
                    wfut = rt.submit_upsert(new_x, new_iv)
            replies = [f.result(timeout=120) for f in futs]
            s = rt.stats()
        pre = sum(1 for r in replies if r.index is not engine.index)
        print(f"[serve] async runtime: {s['completed']} served "
              f"({s['rejected']} rejected, {wfut.result()} docs upserted "
              f"mid-stream; {pre} answered pre-write snapshot) "
              f"QPS {s['qps']:,.1f}  p50 {s['p50_ms']:.1f}ms  "
              f"p99 {s['p99_ms']:.1f}ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
