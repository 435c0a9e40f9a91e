"""The trace reduction, the roofline arithmetic and the window's rate, on small inputs."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import tracing  # noqa: E402
from tracing import Event  # noqa: E402

DEV = "/device:TPU:0"


def _trace():
    # window [10, 20); device ops: [9, 11), [12, 13), [12.5, 14), [18, 21)
    ops = [Event("fusion.3", 9.0, 11.0, device=DEV),
           Event("expand_score", 12.0, 13.0, device=DEV),
           Event("expand_score.7", 12.5, 14.0, device=DEV),
           Event("beam_merge.2", 18.0, 21.0, device=DEV)]
    return tracing.Trace(ops, (10.0, 20.0))


def test_busy_union_and_idle_share():
    tr = _trace()
    assert tr.busy_intervals() == [[10.0, 11.0], [12.0, 14.0], [18.0, 20.0]]
    assert tr.busy_s() == pytest.approx(5.0)
    assert tr.idle_share() == pytest.approx(0.5)
    assert tr.gaps() == [(11.0, 12.0), (14.0, 18.0)]


def test_kernel_events():
    tr = _trace()
    assert [e.name for e in tr.kernel("expand_score")] == ["expand_score", "expand_score.7"]
    assert sum(e.dur for e in tr.kernel("expand_score")) == pytest.approx(2.5)
    assert tr.kernel("expand_score_pq") == []


def test_breakdown():
    bd = _trace().breakdown()
    assert bd["device_ops"][0] == ["expand_score", pytest.approx(2.5)]
    assert dict(map(tuple, bd["device_ops"]))["beam_merge"] == pytest.approx(2.0)
    assert bd["idle_gaps"] == [["unattributed", pytest.approx(4.0)],
                               ["unattributed", pytest.approx(1.0)]]


def test_idle_share_is_none_without_device_ops():
    tr = tracing.Trace([], (0.0, 1.0))
    assert tr.idle_share() is None


def test_call_shapes_and_roofline():
    # An op event as a v5e trace names it: the instruction's HLO text.
    text = ("%expand_score.7 = f32[256,384]{1,0:T(8,128)S(1)} custom-call("
            "s32[98304]{0:T(1024)S(1)} %reshape.339, f32[256,96]{1,0:T(8,128)S(1)} "
            "%copy-done.9, f32[12500,8,128]{2,1,0:T(8,128)} %get-tuple-element.367), "
            'custom_call_target="tpu_custom_call", operand_layout_constraints={'
            "s32[98304]{0}, f32[256,96]{1,0}, f32[12500,8,128]{2,1,0}}")
    ev = tracing.device_event(text, 0.0, 1e-3, {}, DEV)
    assert ev.name == "expand_score.7" and ev.stats["hlo_text"] == text
    shapes = harness.call_shapes(ev)
    assert shapes["out"] == (256, 384)
    assert shapes["args"] == [(98304,), (256, 96), (12500, 8, 128)]
    tr = tracing.Trace([ev], (0.0, 1.0))
    root = pathlib.Path(__file__).resolve().parents[2]
    run = harness.Run(harness.Cell("c", {}, {}, {}, [], [], root), None,
                      (0.0, 1.0), tr, {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    nbytes = 4 * (256 * 384 * 96 + 2 * 256 * 384 + 256 * 96)
    assert run.roofline("expand_score") == pytest.approx(
        100 * nbytes / 819e9 / 1e-3)
    assert run.roofline("expand_score_pq") is None
    assert tracing.device_event("fusion.3", 0.0, 1.0, {}, DEV).name == "fusion.3"


def test_answer_rate_counts_the_straddling_batch_by_its_share():
    # Batches of 4 answered every 2 s from t=2; the window is [0, 9).
    done = np.repeat([2.0, 4.0, 6.0, 8.0, 10.0, 12.0], 4) + np.tile([0, 1e-3, 2e-3, 3e-3], 6)
    # 16 answered by 8.003, and the batch at 10.0 did (9 - 8.003) / (10 - 8.003)
    # of its work inside the window.
    want = (16 + 4 * (9 - 8.003) / (10.0 - 8.003)) / 9
    assert harness.answer_rate(done, 0.0, 9.0) == pytest.approx(want)
    # No answer after the close: the count alone.
    assert harness.answer_rate(done, 0.0, 20.0) == pytest.approx(24 / 20)
    # Answers spread evenly: the correction is at most one answer.
    even = np.arange(1, 1001) * 0.01
    assert harness.answer_rate(even, 0.0, 5.005) == pytest.approx(500.5 / 5.005, rel=1e-3)
