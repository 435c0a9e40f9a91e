#!/usr/bin/env python3
"""The control of a cell's check, at the cell's own size, on the chip.

    python3 bench/control.py --workload deep96-f32.read-closed \\
        --seeds 1,2,3 --queries 8192

For each seed it makes the cell's data and query stream as a run does and
puts the reference, computed in bfloat16 (the precision below the
configuration's f32), in the program's place: its answers, with their
distances, go through the run's own check (``harness.check``).  It prints
the compared numbers of each seed beside their limits and whether the
check called them correct; the control has to come out not correct on
every seed.  No index is built.  The benchmark's own runs never run this.
Run from the root of a checkout, on a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path.cwd()


def readings(cell, seed: int, n_queries: int) -> tuple[dict, bool]:
    """The compared numbers of the bfloat16 control for one seed, and
    whether the run's check calls them correct."""
    import corpus
    import harness
    import reference

    data = corpus.make(cell.config, cell.traffic, seed)
    q = np.arange(n_queries) % len(data["qv"])
    k = int(cell.traffic["k"])
    ids, dist = reference.topk(data["ref_x"], data["ref_iv"], data["qv"][q],
                               data["qint"][q], data["qflt"][q], k, low=True)
    ans = dict(q=q, seq=np.zeros(n_queries, np.int32), ids=ids, dist=dist)
    checks, correct, _ = harness.check(ans, data, cell.config["limits"], k=k,
                                       asked=n_queries)
    return checks, correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--queries", type=int, required=True)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        checks, correct = readings(cell, seed, args.queries)
        out.append(dict(seed=seed, correct=correct, checks=checks))
        print(json.dumps(out[-1]), file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "control": "bfloat16 reference",
                      "seeds": out}))
    return 0 if not any(o["correct"] for o in out) else 1


if __name__ == "__main__":
    sys.exit(main())
