"""Kernel work counts from call shapes, checked by hand for known shapes."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402

WORK = pathlib.Path(__file__).resolve().parents[1] / "work"


def test_expand_score_counts():
    w = harness.load_module(WORK / "expand_score.py")
    # 256 queries x 512 candidates of 96 f32: rows 50,331,648 B, ids and
    # distances 524,288 B each, queries 98,304 B.
    ops, nbytes = w.work({"out": (256, 512), "args": [(131072,), (256, 96), (1, 8, 128)]})
    assert ops == 3 * 256 * 512 * 96 == 37_748_736
    assert nbytes == 50_331_648 + 2 * 524_288 + 98_304 == 51_478_528


def test_expand_score_pq_counts():
    w = harness.load_module(WORK / "expand_score_pq.py")
    # 256 queries x 512 candidates of 64 one-byte codes: codes 8,388,608 B,
    # tables 256 x 256 x 64 x 4 = 16,777,216 B, ids and distances 524,288 B each.
    ops, nbytes = w.work({"out": (256, 512), "args": [(131072,), (256, 256, 64), (1, 32, 128)]})
    assert ops == 2 * 256 * 512 * 64 == 16_777_216
    assert nbytes == 8_388_608 + 16_777_216 + 2 * 524_288 == 26_214_400


def test_peaks_table_names_v5e_and_refuses_unknown_kinds():
    root = pathlib.Path(__file__).resolve().parents[2]
    p = harness.load_peaks(root, "TPU v5 lite")
    assert p["ops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    try:
        harness.load_peaks(root, "TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
