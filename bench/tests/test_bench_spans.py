"""The program's spans in a trace: loading them, naming idle gaps by them,
splitting device time by scope, and the readers that use them."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import spans  # noqa: E402
import tracing  # noqa: E402
from spans import Span  # noqa: E402
from tracing import Event  # noqa: E402

from test_bench_rehearsal import SEED, root, short_drain  # noqa: E402,F401

DEV = "/device:TPU:0"
DISPATCH, COMPLETE = "/host:CPU#1", "/host:CPU#2"
PEAKS = {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ES_TEXT = ("%expand_score.7 = f32[256,384]{1,0:T(8,128)S(1)} custom-call("
           "s32[98304]{0:T(1024)S(1)} %reshape.339, f32[256,96]{1,0:T(8,128)S(1)} "
           "%copy-done.9, f32[12500,8,128]{2,1,0:T(8,128)} %get-tuple-element.367), "
           'custom_call_target="tpu_custom_call"')


def _ops():
    # window [10, 20); busy [10, 11), [12, 14), [18, 20); gaps [11, 12), [14, 18)
    return [Event("fusion.3", 9.0, 11.0, {"tf_op": "jit(f)/while/body/ug.dedup/sort"},
                  device=DEV),
            tracing.device_event(ES_TEXT, 12.0, 13.0, {}, DEV),
            tracing.device_event(ES_TEXT, 12.5, 14.0, {}, DEV),
            Event("beam_merge.2", 18.0, 21.0, {"tf_op": "jit(f)/ug.entry/ug.merge"},
                  device=DEV)]


def _spans():
    return [
        # dispatcher: pack over [10.5, 11.8) with launch inside it over
        # [11.2, 11.9); then blocked on the in-flight queue
        Span("serve.pack", 10.5, 11.2, {"batch": 3}, thread=DISPATCH),
        Span("serve.launch", 11.2, 11.9, {"batch": 3}, thread=DISPATCH),
        Span("search.entry", 11.3, 11.85, thread=DISPATCH),
        Span("serve.inflight_put", 11.9, 19.0, {"batch": 3}, thread=DISPATCH),
        # completer: waiting on the device until 11.4, resolving over
        # [13.9, 16.5)
        Span("serve.device_wait", 9.0, 11.4, {"batch": 2}, thread=COMPLETE),
        Span("serve.resolve", 13.9, 16.5, {"batch": 2, "live": 200, "bucket": 256,
                                           "width": 4, "iters": 100,
                                           "steps_sum": 40000}, thread=COMPLETE),
        Span("serve.pack", 19.5, 19.9, {"batch": 4}, thread=DISPATCH),
        Span("serve.launch", 19.9, 20.6, {"batch": 4}, thread=DISPATCH),
        Span("serve.resolve", 21.0, 21.5, {"batch": 3, "live": 256, "bucket": 256,
                                           "width": 4, "iters": 50,
                                           "steps_sum": 1}, thread=COMPLETE),
    ]


def _trace(with_spans=True):
    tr = tracing.Trace(_ops(), (10.0, 20.0))
    if with_spans:
        tr.spans = _spans()
    return tr


def _run(tr):
    cell = harness.load_cell(ROOT, "deep96-f32.read-closed")
    return harness.Run(cell, None, (10.0, 20.0), tr, PEAKS)


def test_gaps_take_the_name_of_the_innermost_span_that_covers_most():
    tr = _trace()
    # [11, 12): on the dispatcher, launch (0.7) and its child search.entry
    # (0.55) cover more than half, and the child is innermost; it covers
    # more than the completer's device_wait (0.4).
    # [14, 18): the dispatcher's inflight_put covers all 4 s, the
    # completer's resolve 2.5 s.
    assert spans.idle_gaps(tr) == [["serve.inflight_put", pytest.approx(4.0)],
                                   ["search.entry", pytest.approx(1.0)]]
    assert spans._name_gap(tr.spans, 11.0, 12.0) == ("search.entry",
                                                     pytest.approx(0.55))
    # [10, 11): device_wait covers all of it, the dispatcher's pack half.
    assert spans._name_gap(tr.spans, 10.0, 11.0)[0] == "serve.device_wait"
    # No span in [30, 31): unattributed.
    assert spans._name_gap(tr.spans, 30.0, 31.0) == ("unattributed", 0.0)


def test_a_tie_between_threads_goes_to_the_shorter_span():
    ss = [Span("serve.inflight_put", 0.0, 10.0, thread=DISPATCH),
          Span("serve.device_wait", 1.0, 5.0, thread=COMPLETE)]
    assert spans._name_gap(ss, 2.0, 3.0)[0] == "serve.device_wait"


def test_idle_by_span_and_gap_size():
    got = spans.idle_by_span(_trace())
    assert got["idle_s"] == pytest.approx(5.0)
    assert got["in_spans_share"] == pytest.approx(1.0)
    assert got["by_span"] == {"serve.inflight_put": pytest.approx(4.0),
                              "search.entry": pytest.approx(1.0)}
    assert got["by_gap_size"] == {"<1ms": 0.0, "1-10ms": 0.0, "10-100ms": 0.0,
                                  ">=100ms": pytest.approx(5.0)}
    # Spans over half the idle time only.
    tr = _trace()
    tr.spans = [Span("serve.pack", 15.0, 19.0, thread=DISPATCH)]
    assert spans.idle_by_span(tr)["in_spans_share"] == pytest.approx(3.0 / 5.0)


def test_device_ops_split_by_innermost_scope():
    ops = dict(map(tuple, spans.device_ops(_trace())))
    assert ops == {"expand_score": pytest.approx(2.5),
                   "ug.merge:beam_merge": pytest.approx(2.0),
                   "ug.dedup:fusion": pytest.approx(1.0)}
    assert spans.scope(Event("x", 0, 1, {"tf_op": "jit(f)/while/body/dedup"})) is None
    assert spans.op_events_per_s(_trace()) == pytest.approx(3 / 10)


def test_the_span_readers():
    run = _run(_trace())

    def read(name):
        return harness.load_module(BENCH / "metrics" / f"{name}.py").read(run)

    # Idle and in pack or launch: [11, 11.9) of the gap [11, 12).
    assert read("idle_preparing_share.qps") == pytest.approx(100 * 0.9 / 10)
    # Packs that start in the window: 0.7 s (batch 3) and 0.4 s (batch 4).
    assert read("serve_pack_ms") == pytest.approx(1e3 * 0.55)
    # Their launches: 0.7 s and 0.7 s, the second running past the window.
    assert read("search_launch_ms") == pytest.approx(1e3 * 0.7)
    # One resolve starts in the window: 40,000 of 100 * 4 * 256 slots.
    assert read("beam_slot_use") == pytest.approx(100 * 40000 / (100 * 4 * 256))
    bare = _run(_trace(with_spans=False))
    for name in ("idle_preparing_share.qps", "serve_pack_ms", "search_launch_ms",
                 "beam_slot_use"):
        assert harness.load_module(BENCH / "metrics" / f"{name}.py").read(bare) is None


def test_spans_leave_the_existing_metrics_as_they_were():
    bare, spanned = _run(_trace(with_spans=False)), _run(_trace())
    for name in ("device_idle_share.qps", "expand_score_roofline"):
        reader = harness.load_module(BENCH / "metrics" / f"{name}.py")
        assert reader.read(spanned) == reader.read(bare) is not None
    assert spanned.trace.busy_s() == bare.trace.busy_s()
    assert spanned.trace.breakdown() == bare.trace.breakdown()


def test_load_reads_the_program_spans_from_a_trace(tmp_path):
    import jax

    from repro.trace import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("serve.pack", batch=7) as sp:
            with span("search.flags"):
                pass
            sp.set_metadata(live=5)
        with span("bench.other"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = sorted(spans.load(str(tmp_path)), key=lambda s: s.start)
    assert [s.name for s in got] == ["serve.pack", "search.flags"]
    assert got[0].stats == {"batch": 7, "live": 5}
    assert got[0].thread == got[1].thread
    assert got[0].start <= got[1].start <= got[1].end <= got[0].end


def test_trace_cell_reads_the_spans_of_a_tiny_run(root):
    import trace_cell

    out = trace_cell.run(root, "tiny.closed", SEED, 1.0, t_start=harness.clock())
    assert out["correct"], out["checks"]
    assert out["metrics"] == {}          # no device ops on the CPU
    got = out["spans"]
    assert got["n_spans"] > 0
    assert got["idle"]["in_spans_share"] > 0.5
    assert all(name != "unattributed" for name, _ in got["idle_gaps"])
    slot_use = got["metrics"].get("beam_slot_use")
    assert slot_use is None or 0 < slot_use <= 100
    json.dumps(out)
