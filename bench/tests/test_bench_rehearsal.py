"""Each loop of the harness, end to end on the CPU at a tiny size, and the
faults its check has to catch.

The harness's look for a chip is skipped (``harness.run_cell`` is called
directly); everything else of a run is driven: data, build, runtime,
warm-up, the window, the reference and the check.  A sound run passes the
check; a run with the timed path broken underneath does not.
"""
import json
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import corpus  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import run_cell  # noqa: E402

SEED = 2**31 + 12345          # past 32 signed bits, as the check's seeds are


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding a tiny closed-loop cell over the real harness,
    metrics and work files, with its traffic made here."""
    r = tmp_path_factory.mktemp("tiny")
    (r / "bench" / "configs").mkdir(parents=True)
    (r / "bench" / "traffic").mkdir()
    for d in ("metrics", "work", "peaks.json"):
        (r / "bench" / d).symlink_to(BENCH / d)
    cfg = json.loads((BENCH / "configs" / "deep96-f32.json").read_text())
    cfg.update(rows=600, dim=8, build=dict(
        exact_spatial=True, max_edges_if=16, max_edges_is=16, ef_spatial=32,
        ef_attribute=32, iterations=2, repair_width=8))
    (r / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = dict(loop="closed", clients=40, submitters=2, max_batch=8,
               max_queue=65536, width=4, ef=64, k=10, semantics=["IF", "IS", "RF", "RS"],
               windows=dict(half_min=0.05, half_max=0.2, point_for=["RS"]),
               query_pool=256, trace_seconds=0.2)
    (r / "bench" / "traffic" / "tiny-closed.json").write_text(json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = "tiny.closed"
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    spec["configs"] = [dict(name="tiny", source="x", file="bench/configs/tiny.json",
                            reduced=[], why="x")]
    spec["workloads"] = [dict(name=cell, config="tiny", traffic="tiny-closed",
                              chips=1, why="x")]
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    return r


@pytest.fixture(autouse=True)
def short_drain(monkeypatch):
    monkeypatch.setattr(harness, "DRAIN_S", 2.0)


def _run(root, cell, trace=False):
    return harness.run_cell(root, cell, SEED, 1.0, trace, t_start=harness.clock())


def test_a_run_passes_its_check_and_reports_its_metrics(root):
    out = _run(root, "tiny.closed")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "qps", "recall_at_10"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks" and out["attempted"] > 0 and out["failed"] == 0


def test_a_traced_run_without_a_chip_reports_no_device_metric(root):
    out = _run(root, "tiny.closed", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"] == {}
    assert out["device"]["busy_s"] == 0.0


def test_the_entry_refuses_without_a_tpu(capsys, monkeypatch):
    monkeypatch.chdir(ROOT)
    rc = run_cell.main(["--workload", "deep96-f32.read-closed", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def _drop_half(self, inflight):
    live, res, index = inflight
    _complete(self, (live[: (len(live) + 1) // 2], res, index))


def _alter_answers(store, *a, **kw):
    res = _search_mixed(store, *a, **kw)
    ids = jnp.where(res.ids >= 0, (res.ids + 1) % store.capacity, -1)
    return res._replace(ids=ids)


import importlib  # noqa: E402

from repro.serve.runtime import ServeRuntime  # noqa: E402

# ``repro.core`` re-exports a function under the module's name.
_search_mod = importlib.import_module("repro.core.search")

_search_mixed = _search_mod.search_mixed
_complete = ServeRuntime._complete


@pytest.mark.parametrize("fault,caught", [
    ("half the batch left out", "unanswered"),
    ("an answer altered where it is produced", "bad_rows"),
])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault, caught):
    if fault.startswith("half"):
        target, attr, broken = ServeRuntime, "_complete", _drop_half
    else:
        target, attr, broken = _search_mod, "search_mixed", _alter_answers
    warm = harness.warm_searches

    def warm_then_break(*a, **kw):   # the fault holds from the window on
        warm(*a, **kw)
        monkeypatch.setattr(target, attr, broken)

    monkeypatch.setattr(harness, "warm_searches", warm_then_break)
    out = _run(root, "tiny.closed")
    assert not out["correct"]
    value, limit = out["checks"][caught]
    assert value > limit, out["checks"]


def test_the_lower_precision_control_fails_the_check(root):
    """The reference in bfloat16, put in the program's place, comes out not
    correct through the run's own check, which the f32 reference passes."""
    cell = harness.load_cell(root, "tiny.closed")
    checks, correct = control.readings(cell, SEED, 256)
    assert not correct
    value, limit = checks["dist_gap"]
    assert value > limit, checks
    data = corpus.make(cell.config, cell.traffic, SEED)
    q = np.arange(256)
    ids, dist = reference.topk(data["ref_x"], data["ref_iv"], data["qv"],
                               data["qint"], data["qflt"], 10)
    ans = dict(q=q, seq=np.zeros(256, np.int32), ids=ids, dist=dist)
    checks, correct, verdict = harness.check(ans, data, cell.config["limits"],
                                             k=10, asked=256)
    assert correct, checks
    assert np.nanmin(verdict["recall"]) == 1.0
