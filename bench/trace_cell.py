#!/usr/bin/env python3
"""Run one cell traced, as ``run_cell.py --trace 1`` does, and read the
program's own spans and scopes from the same trace.

    python3 bench/trace_cell.py --workload <cell> --seed <n> --seconds <s> [--out <file>]

``tracing.load`` keeps the device ops and the window only, so the readers
that need the program's spans (``bench/metrics/{idle_preparing_share.qps,
serve_pack_ms,search_launch_ms,beam_slot_use}.py``) find nothing in a
``run_cell.py`` run.  This entry gives the trace its spans
(``spans.load``) before the readers run, and prints ``run_cell.py``'s
result line with one more key, ``spans``: every reader under
``bench/metrics/`` that reads something, the idle gaps named by the
program span that covers most of each, the idle time by span and by gap
size, device time split by ``ug.*`` scope, the device op events per
second, and the stats of one event of each device op, to read by hand.
``--out`` also writes the line to a file.  Exits non-zero, printing no
result, when JAX's first device is not a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path.cwd()


def op_stats(trace, per_op: int = 1, width: int = 600) -> dict:
    """The stats of the first ``per_op`` events of each device op (``.N``
    suffixes merged), strings cut to ``width`` characters."""
    out = {}
    for e in trace.ops:
        name = re.sub(r"(\.\d+)+$", "", e.name)
        seen = out.setdefault(name, [])
        if len(seen) < per_op:
            seen.append({k: v[:width] if isinstance(v, str) else v
                         for k, v in e.stats.items()})
    return out


def run(root, workload: str, seed: int, seconds: float, *, t_start: float,
        log=sys.stderr) -> dict:
    """``harness.run_cell`` traced, with the program's spans read too."""
    import harness
    import spans
    import tracing

    traces = []
    load = tracing.load

    def load_with_spans(tdir):
        tr = load(tdir)
        tr.spans = spans.load(tdir)
        traces.append(tr)
        return tr

    tracing.load = load_with_spans
    try:
        out = harness.run_cell(root, workload, seed, seconds, True,
                               t_start=t_start, log=log)
    finally:
        tracing.load = load
    (tr,) = traces
    cell = harness.load_cell(root, workload)
    kind = out["device"]["kind"]
    peaks = harness.load_peaks(cell.root, kind) if out["device"]["platform"] == "tpu" else {}
    run_ = harness.Run(cell, None, None, tr, peaks)
    read = {}
    for path in sorted((cell.root / "bench" / "metrics").glob("*.py")):
        v = cell.reader(path.stem).read(run_)
        if v is not None:
            read[path.stem] = float(v)
    out["spans"] = {
        "metrics": read,
        "n_spans": len(tr.spans),
        "op_events_per_s": spans.op_events_per_s(tr),
        "idle": spans.idle_by_span(tr),
        "idle_gaps": spans.idle_gaps(tr),
        "device_ops": spans.device_ops(tr),
        "op_stats": op_stats(tr),
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"trace_cell: needs a TPU, but JAX's first device is on platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    line = json.dumps(run(ROOT, args.workload, args.seed, args.seconds,
                          t_start=T_START))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
