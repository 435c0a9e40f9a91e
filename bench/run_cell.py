#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``.  The cell, its configuration, its traffic
and its per-layer metrics are found by name from ``BENCHMARK.json``.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window.  Exits non-zero, printing no result, when JAX's first device
is not a TPU or there are fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path.cwd()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "BENCHMARK.json").is_file() or not (ROOT / "src" / "repro").is_dir():
        print("run_cell: run from the root of a checkout that holds "
              "BENCHMARK.json and src/repro", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"run_cell: needs a TPU, but JAX's first device is on platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
