"""Benchmark driver: one function per paper table (DESIGN.md §7).

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks corpora for
smoke runs; ``--only <prefix>[,<prefix>…]`` filters benches; ``--json PATH``
additionally writes the rows as a JSON artifact — one schema across build,
search and updates benches, the CI perf-trajectory surface.

Perf gate (DESIGN.md §11): ``--check BENCH_baseline.json`` compares the
produced rows against committed thresholds and exits non-zero on a
recall or peak-bytes regression (or a disappeared row);
``--write-baseline PATH`` derives those thresholds from the current run
(recall floor −0.03, peak-bytes ceiling ×1.25).
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
import traceback

_METRIC = re.compile(r"(\w+)=([-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?)\b")

RECALL_SLACK = 0.03     # committed floor = measured recall − slack
BYTES_HEADROOM = 1.25   # committed ceiling = measured bytes × headroom


def parse_metrics(derived: str) -> dict[str, float]:
    """Extract ``key=value`` numeric metrics from a row's derived column."""
    return {k: float(v) for k, v in _METRIC.findall(derived)}


def gated_metrics(derived: str) -> tuple[dict, dict]:
    """(min-bounded, max-bounded) metrics of one row: recalls are floors,
    byte counts are ceilings.  QPS/latency stay ungated (noisy on shared CI
    runners); recall and traced peak-bytes are deterministic.  Comparison
    yardsticks (``recall_fresh_rebuild``) are not gated — they measure the
    baseline builder, not the code under test."""
    m = parse_metrics(derived)
    mins = {
        k: v for k, v in m.items()
        if k.startswith("recall") and "fresh" not in k
    }
    maxs = {k: v for k, v in m.items() if k.endswith("bytes")}
    return mins, maxs


def write_baseline(rows: list[dict], path: str) -> None:
    base = {}
    for r in rows:
        mins, maxs = gated_metrics(r["derived"])
        if not mins and not maxs:
            continue
        base[r["name"]] = {
            "min": {k: round(max(v - RECALL_SLACK, 0.0), 3) for k, v in mins.items()},
            "max": {k: int(v * BYTES_HEADROOM) for k, v in maxs.items()},
        }
    with open(path, "w") as f:
        json.dump({"schema": 1, "rows": base}, f, indent=2, sort_keys=True)
        f.write("\n")


def check_baseline(rows: list[dict], path: str) -> list[str]:
    """Compare rows against a committed baseline; return violation strings."""
    try:
        with open(path) as f:
            base = json.load(f)["rows"]
    except FileNotFoundError:
        return [f"baseline {path} not found — commit it "
                f"(benchmarks/run.py --write-baseline {path})"]
    by_name = {r["name"]: r for r in rows}
    problems = []
    for name, gate in base.items():
        row = by_name.get(name)
        if row is None:
            problems.append(f"{name}: row missing from this run "
                            f"(bench removed or crashed)")
            continue
        m = parse_metrics(row["derived"])
        for key, floor in gate.get("min", {}).items():
            if key not in m:
                problems.append(f"{name}: metric {key} disappeared")
            elif m[key] < floor:
                problems.append(
                    f"{name}: {key}={m[key]:.3f} below baseline floor {floor}")
        for key, ceil in gate.get("max", {}).items():
            if key not in m:
                problems.append(f"{name}: metric {key} disappeared")
            elif m[key] > ceil:
                problems.append(
                    f"{name}: {key}={m[key]:.0f} above baseline ceiling {ceil}")
    return problems


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpora for CI regression output (implies --quick)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench-name prefixes")
    ap.add_argument("--n", type=int, default=None, dest="n_override",
                    help="override the corpus size for every n-parameterized "
                         "bench (e.g. --n 1000000 --only memory,build pushes "
                         "the plane-frontier and build tables to large n; "
                         "builds above the exact-spatial cutoff go through "
                         "the on-device sharded path)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to PATH as a JSON artifact")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="fail on recall/peak-bytes regression against a "
                         "committed baseline JSON (the CI perf gate)")
    ap.add_argument("--write-baseline", default=None, metavar="PATH",
                    help="derive and write baseline thresholds from this run")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import tables

    if args.smoke:
        args.quick = True
    n = (600 if args.smoke else 2000) if args.quick else None
    build_sizes = (400,) if args.smoke else ((800, 1600) if args.quick else (1000, 2000, 4000))
    if args.n_override:
        n = args.n_override
        build_sizes = (args.n_override,)
    benches = [
        ("ifann", lambda: tables.bench_ifann(**({"n": n} if n else {}))),
        ("query_types", lambda: tables.bench_query_types(**({"n": n} if n else {}))),
        ("workloads", lambda: tables.bench_workloads(**({"n": n} if n else {}))),
        ("indexing", lambda: tables.bench_indexing(**({"n": n} if n else {}))),
        ("vary_k", lambda: tables.bench_k(**({"n": n} if n else {}))),
        ("sensitivity", lambda: tables.bench_sensitivity(n=1200 if args.quick else 2000)),
        ("scalability", lambda: tables.bench_scalability(
            sizes=(500, 1000, 2000) if args.quick else (1000, 2000, 4000, 8000))),
        ("beam_sweep", lambda: tables.bench_beam_sweep(**({"n": n} if n else {}))),
        ("mixed_workload", lambda: tables.bench_mixed_workload(
            **({"n": n} if n else {}),
            require_speedup=2.0 if args.smoke else None)),
        ("build", lambda: tables.bench_build(sizes=build_sizes)),
        ("updates", lambda: tables.bench_updates(
            **({"n": n} if n else {}),
            require_recall_gap=0.05 if args.smoke else None)),
        ("memory", lambda: tables.bench_memory(
            **({"n": n} if n else {}),
            require_reduction=3.0 if args.smoke else None)),
        ("serve", lambda: tables.bench_serve(
            **({"n": n} if n else {}),
            require_qps_ratio=0.85 if args.smoke else None)),
        ("kernels", tables.bench_kernels),
        ("lm_steps", tables.bench_lm_steps),
    ]
    only = args.only.split(",") if args.only else None
    print("name,us_per_call,derived")
    failures = 0
    all_rows = []
    for name, fn in benches:
        if only and not any(name.startswith(p) for p in only):
            continue
        t0 = time.time()
        try:
            for r in fn():
                all_rows.append(r)
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
            print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(all_rows, f, indent=2)
    if args.write_baseline:
        write_baseline(all_rows, args.write_baseline)
        print(f"# baseline written to {args.write_baseline}", file=sys.stderr)
    if args.check:
        problems = check_baseline(all_rows, args.check)
        for p in problems:
            print(f"# REGRESSION {p}", file=sys.stderr)
        if problems:
            print(f"# perf gate: {len(problems)} regression(s) against "
                  f"{args.check}", file=sys.stderr)
            failures += 1
        else:
            print(f"# perf gate: clean against {args.check}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
