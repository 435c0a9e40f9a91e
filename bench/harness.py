"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name from its entry in
``BENCHMARK.json``: the configuration file it names, ``bench/traffic/
<traffic>.json``, a reader ``bench/metrics/<metric>.py`` for each
per-layer metric, ``bench/work/<kernel>.py`` for each kernel whose
roofline is read, and ``bench/peaks.json``.  A new cell, mix,
configuration or metric is new files and a new entry; nothing here needs
an edit.

From the program the bench takes the system under test (``UGIndex.build``,
``with_dtype``, ``ServeEngine``, ``ServeRuntime``) and the names of its
programs and kernels in the trace.  Data, traffic, the reference, the
trace reduction and the arithmetic of every metric live in ``bench/``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import re
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

import corpus
import loadgen
import reference
import tracing
from loadgen import clock

DRAIN_S = 60.0          # how long past the window's close an answer may come
WARM_S = 1200.0         # how long a warm-up request may take, compile included
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------- discovery
@dataclasses.dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: pathlib.Path

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def load_module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {', '.join(cells)})")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[entry["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and any(e["name"] == m["moves"] for e in e2e)]
    return Cell(name, entry, config, traffic, e2e, layer, root)


# ------------------------------------------------------------- compiles
class CompileCounter:
    """Programs compiled or loaded from the persistent cache (each is one
    backend-compile event), with the time of each."""

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.times.append(clock())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t < b for t in self.times)


def enable_compile_cache(root: pathlib.Path) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``:
    a fixed path, so every run of a cell in a checkout finds the programs
    the first one compiled.  Every program is kept, however short its
    compile, so that set-up loads rather than compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------- set-up
def build_index(config: dict, data: dict, seed: int):
    import jax

    from repro.core import UGConfig, UGIndex

    idx = UGIndex.build(data["x"], data["ivs"], UGConfig(**config["build"]),
                        seed=seed & 0x7FFFFFFF)
    if config["plane"] != "f32":
        idx = idx.with_dtype(config["plane"], pq_m=config.get("pq_m"))
    jax.block_until_ready(idx.store)
    return idx


def warm_searches(rt, data, traffic, sizes) -> None:
    """One micro-batch of each size through the stopped runtime, pumped
    inline so that the coalescer sees each batch whole: the search
    programs (and entry acquisition) at each bucket the window uses."""
    for b in sizes:
        futs = [rt.submit(data["qv"][i], data["qint"][i], int(data["qflag"][i]),
                          ef=int(traffic["ef"]), k=int(traffic["k"]))
                for i in range(b)]
        rt.run_until_idle()
        for f in futs:
            f.result(timeout=WARM_S)


# ------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """What a per-layer reader reads: the trace, the request record and
    the window."""
    cell: Cell
    rec: loadgen.Record
    window: tuple            # (t0, t1) on the host clock
    trace: tracing.Trace | None
    peaks: dict

    def work(self, kernel: str):
        return load_module(self.cell.root / "bench" / "work" / f"{kernel}.py")

    def roofline(self, kernel: str) -> float | None:
        """Least time over measured time of one kernel, in percent: the
        least time of each launch is the larger of its operations over the
        op peak and its bytes over the HBM peak, from its call shapes."""
        if self.trace is None:
            return None
        evs = self.trace.kernel(kernel)
        spent = sum(e.dur for e in evs)
        if not evs or spent <= 0:
            return None
        work = self.work(kernel)
        least = 0.0
        for e in evs:
            try:
                ops, nbytes = work.work(call_shapes(e))
            except ValueError as err:     # no shapes: nothing to read
                print(f"[bench] {kernel}_roofline not read: {err}", file=sys.stderr)
                return None
            least += max(ops / self.peaks["ops_per_s"],
                         nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / spent

    def idle_pct(self) -> float | None:
        share = None if self.trace is None else self.trace.idle_share()
        return None if share is None else 100.0 * share


_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_OP = re.compile(r"\s[a-z][a-z0-9_-]*\(")


def call_shapes(event) -> dict:
    """Shapes of a kernel launch from its trace event's HLO text
    (``%name = f32[B,C]{..} custom-call(s32[..] %a, ...), attrs``): ``out``,
    the result, and ``args``, the operands, each a tuple of dims."""
    text = ""
    for key in ("hlo_text", "long_name"):
        v = event.stats.get(key)
        if isinstance(v, str) and "[" in v:
            text = v
            break
    if not text:
        raise ValueError(f"trace event {event.name} carries no shapes: "
                         f"{sorted(event.stats)}")
    rhs = text.partition("=")[2]
    op = _OP.search(rhs)
    res, args = (rhs[:op.start()], rhs[op.end():]) if op else (rhs, "")
    args = args.partition("), ")[0]
    shape = lambda m: tuple(int(x) for x in m.group(2).split(",") if x)
    out = [shape(m) for m in _SHAPE.finditer(res)]
    return {"out": out[0] if out else (),
            "args": [shape(m) for m in _SHAPE.finditer(args)]}


def load_peaks(root: pathlib.Path, kind: str) -> dict:
    peaks = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def serve(cell: Cell, seed: int, say):
    """Set-up: data, build, runtime, and the warm-up of the one program
    shape a closed window uses, the full batch."""
    from repro.serve.engine import ServeEngine
    from repro.serve.runtime import RuntimeConfig, ServeRuntime

    cfg, tr = cell.config, cell.traffic
    say(f"cache {enable_compile_cache(cell.root)}")
    compiles = CompileCounter()
    data = corpus.make(cfg, tr, seed)
    idx = build_index(cfg, data, seed)
    say(f"built {cfg['rows']} rows, plane {cfg['plane']}, "
        f"{len(compiles.times)} programs compiled or loaded so far")
    engine = ServeEngine(model=None, params=None)
    engine.attach_index(idx, width=int(tr["width"]))
    del idx
    max_batch = int(tr["max_batch"])
    rt = ServeRuntime(engine, RuntimeConfig(
        max_batch=max_batch, max_queue=int(tr["max_queue"]),
        default_ef=int(tr["ef"]), default_k=int(tr["k"])))
    warm_searches(rt, data, tr, [max_batch])
    say(f"warmed; {len(compiles.times)} programs so far")
    return data, rt, compiles


def answer_rate(done: np.ndarray, t0: float, t1: float) -> float:
    """Answers per second over ``[t0, t1)``.  The answers that come
    together first after ``t1`` (within half the time since the answer
    before them) count by the share of that time which lies inside the
    window: the batch answered just after the close did that share of its
    work inside it, and the rate does not move in whole batches."""
    ts = np.sort(done[done >= t0])
    n = int(np.searchsorted(ts, t1))
    if n == len(ts):
        return n / (t1 - t0)
    t_b = ts[n - 1] if n else t0
    t_a = ts[n]
    m = int(np.searchsorted(ts, t_a + (t_a - t_b) / 2, side="right")) - n
    return (n + m * (t1 - t_b) / (t_a - t_b)) / (t1 - t0)


def check(ans: dict, data: dict, limits: dict, *, k: int, asked: int,
          truth=None) -> tuple[dict, bool, dict]:
    """Judge the answers of a run against the reference.  ``ans`` holds
    the answered queries (``q``, ``seq``, ``ids``, ``dist``; ``seq`` is -1
    where an answer names no index the run served), ``asked`` how many
    were submitted.  Returns the compared numbers, each as ``[value,
    limit]``, whether every one holds, and the per-query verdict."""
    verdict = reference.judge(ans, data, k=k, truth=truth)
    rec_sem = reference.recall_by_semantics(
        verdict["recall"], verdict["sem"], corpus.SEMANTICS)
    verdict["by_semantics"] = rec_sem
    checks = {
        "unanswered": [int(asked - len(ans["q"])), limits["unanswered"]],
        "unnamed_snapshot": [int(np.sum(ans["seq"] < 0)), 0],
        "bad_rows": [verdict["bad_rows"], limits["bad_rows"]],
        "dist_gap": [verdict["dist_gap"], limits["dist_gap"]],
    }
    correct = all(v <= lim for v, lim in checks.values())
    return checks, correct, verdict


def run_cell(root, name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, log=sys.stderr) -> dict:
    """Run ``name`` once and return the result line as a dict."""
    import jax

    cell = load_cell(root, name)
    tr = cell.traffic
    if tr["loop"] != "closed":
        raise KeyError(f"traffic {cell.entry['traffic']!r}: no loop {tr['loop']!r}")
    say = lambda *a: print(f"[bench {clock() - t_start:8.3f}s]", *a, file=log, flush=True)
    dev = jax.devices()[0]
    n_chips = int(cell.entry["chips"])
    if len(jax.devices()) < n_chips:
        raise RuntimeError(f"cell {name} needs {n_chips} chips, JAX sees "
                           f"{len(jax.devices())}")
    peaks = load_peaks(cell.root, dev.device_kind) if dev.platform == "tpu" else {}
    say(f"device {dev.device_kind}")
    data, rt, compiles = serve(cell, seed, say)
    snaps = {id(rt.engine.index): 0}
    k = int(tr["k"])

    # ---- the window
    span = float(seconds)
    clients = int(tr["clients"])
    rec = loadgen.Record(clients * 4 + 100000, k)
    gen = loadgen.Generator(rt, data, tr, rec, snaps)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = None

    def start():
        rt.start()
        t0 = clock()
        if trace:
            nonlocal tracer
            length = min(float(tr["trace_seconds"]), span / 2)
            tracer = threading.Thread(target=_trace_span, daemon=True, args=(
                tdir, t0 + (span - length) / 2, length))
            tracer.start()
        return t0

    t0 = gen.closed(clients, int(tr["submitters"]), start, lambda t: t + span,
                    DRAIN_S)
    t1 = t0 + span
    setup_s = t0 - t_start
    t_close = max(t1, clock()) + DRAIN_S
    still_open = rec.wait_all(t_close)
    if tracer is not None:
        tracer.join()
    rt.stop()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    say(f"window {span} s: {rec.n} queries, {still_open} unanswered, "
        f"{compiles.between(t0, t1)} programs compiled or loaded in it")
    rt.engine.index = None
    del rt, gen
    gc.collect()

    # ---- the check, against the reference
    n = rec.n
    # An answer that came after the drain never came, for the check.
    answered = (rec.done[:n] <= t_close) & ~rec.err[:n]
    ans = dict(q=rec.q[:n][answered], seq=rec.seq[:n][answered],
               ids=rec.ids[:n][answered], dist=rec.dist[:n][answered])
    checks, correct, verdict = check(ans, data, cell.config["limits"], k=k, asked=n)
    say("recall by semantics " + " ".join(
        f"{s}={r:.4f}" for s, r in verdict["by_semantics"].items()))

    # ---- metrics
    done = rec.done[:n]
    e2e = {}
    for m in cell.end_to_end:
        mname = m["name"]
        if mname == "setup_s":
            v = setup_s
        elif mname == "qps":
            v = answer_rate(done[answered], t0, t1)
        elif mname == "recall_at_10":
            v = float(np.nanmean(verdict["recall"]))
        else:
            raise KeyError(f"no computation for end-to-end metric {mname}")
        e2e[mname] = {"value": v, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_chips, "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": int(n),
           "failed": int(n - answered.sum())}
    if trace:
        tr_obj = tracing.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        run = Run(cell, rec, (t0, t1), tr_obj, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = tr_obj.busy_s()
        device["window_s"] = tr_obj.window_s
        out["device"] = device
        out["breakdown"] = tr_obj.breakdown()
    else:
        out["metrics"] = e2e
        out["device"] = device
    out["checks"] = checks
    for cname, (v, lim) in checks.items():
        say(f"check {cname} {v} limit {lim}")
    return out


def _trace_span(tdir, t_from, length):
    """Trace ``length`` seconds from ``t_from``, inside the bench's window
    span so that its bounds land on the trace's clock."""
    import jax

    wait = t_from - clock()
    if wait > 0:
        time.sleep(wait)
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        time.sleep(length)
    jax.profiler.stop_trace()
