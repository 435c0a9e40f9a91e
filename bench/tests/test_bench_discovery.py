"""Cells are found by name, and a new cell is only new files and entries."""
import json
import pathlib
import re
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import tracing  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = harness.load_cell(ROOT, cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, f"{cell} reports no per-layer metric"
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)
    assert c.config["rows"] > 0 and c.traffic["loop"] == "closed"


def test_benchmark_json_keeps_to_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    cells = {w["name"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert set(c["reduced"]) <= set(json.loads((ROOT / c["file"]).read_text()))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A dummy configuration, traffic mix and per-layer metric, added as
    files beside the existing ones and entries in BENCHMARK.json."""
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("tests"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((BENCH / "configs" / "deep96-f32.json").read_text())
    cfg.update(name="dummy", rows=1234)
    (tmp_path / "bench" / "configs" / "dummy.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "read-closed.json").read_text())
    mix["clients"] = 35
    (tmp_path / "bench" / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "dummy_busy_s.py").write_text(
        "def read(run):\n    return None if run.trace is None else run.trace.busy_s()\n")
    spec["configs"].append(dict(name="dummy", source="x", file="bench/configs/dummy.json",
                                reduced=[], why="x"))
    spec["workloads"].append(dict(name="dummy.mix", config="dummy", traffic="dummy-mix",
                                  chips=1, why="x"))
    spec["per_layer"].append(dict(name="dummy_busy_s", unit="s", better="lower",
                                  source="device_trace", layer="device", moves="setup_s",
                                  workloads=["dummy.mix"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = harness.load_cell(tmp_path, "dummy.mix")
    assert c.config["rows"] == 1234 and c.traffic["clients"] == 35
    assert [m["name"] for m in c.per_layer] == ["dummy_busy_s"]
    tr = tracing.Trace([tracing.Event("fusion", 0.0, 0.25, device="/device:TPU:0")],
                       (0.0, 1.0))
    run = harness.Run(c, None, (0.0, 1.0), tr, {})
    assert c.reader("dummy_busy_s").read(run) == pytest.approx(0.25)
    with pytest.raises(KeyError):
        harness.load_cell(tmp_path, "no.such-cell")
