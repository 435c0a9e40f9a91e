"""The program's own spans and scopes in a profiler trace, and what they
attribute.

The serve runtime and the search open host spans per batch (``serve.*``,
``search.*``; ``src/repro/trace.py``), written by the profiler into the
same ``.xplane.pb`` as the device ops and on the same clock, with their
counts as stats.  The beam program names its parts with ``ug.*``
``jax.named_scope``s, which reach the device op events as metadata.
:func:`load` reads the spans; a :class:`tracing.Trace` that carries them
as ``spans`` is what every function here reduces:

- :func:`idle_gaps`: each idle gap of the device, named by the innermost
  program span that covers most of it;
- :func:`idle_by_span`: the device's idle seconds by that name, by gap
  size, and the share of them inside some program span;
- :func:`device_ops`: device time by op, each op prefixed by its
  innermost ``ug.*`` scope where the event carries one;
- the per-layer readers' numbers: :func:`idle_preparing_share`,
  :func:`serve_pack_ms`, :func:`search_launch_ms`, :func:`beam_slot_use`.

Each returns None where the trace carries no program span.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import tracing

PREFIXES = ("serve.", "search.")
# A scope in an op's name path: ``.../while/body/ug.dedup/sort``.
_SCOPE = re.compile(r"(?:^|/)(ug\.[A-Za-z_]+)(?=/|$)")
GAP_SIZES = ((1e-3, "<1ms"), (1e-2, "1-10ms"), (1e-1, "10-100ms"),
             (float("inf"), ">=100ms"))


@dataclasses.dataclass
class Span(tracing.Event):
    thread: str = ""        # the trace line (one per host thread)


def load(trace_dir: str) -> list[Span]:
    """The program's spans in the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith(tracing.DEVICE_PLANE):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                    dict(ev.stats), thread=f"{plane.name}#{i}"))
    return out


def _spans(trace):
    return getattr(trace, "spans", None) or None


def _overlap(s, a, b) -> float:
    return max(0.0, min(s.end, b) - max(s.start, a))


def _name_gap(spans, a: float, b: float) -> tuple[str, float]:
    """The name of gap ``[a, b)`` and how much of it its span covers.  On
    each thread the innermost span that covers more than half the gap, else
    the one that covers the most; across threads the one that covers more,
    the shorter span on a tie."""
    best = None
    by_thread = collections.defaultdict(list)
    for s in spans:
        if s.end > a and s.start < b:
            by_thread[s.thread].append(s)
    for ss in by_thread.values():
        half = [s for s in ss if _overlap(s, a, b) > (b - a) / 2]
        pick = (min(half, key=lambda s: s.dur) if half else
                max(ss, key=lambda s: (_overlap(s, a, b), -s.dur)))
        key = (_overlap(pick, a, b), -pick.dur)
        if best is None or key > best[0]:
            best = (key, pick)
    return ("unattributed", 0.0) if best is None else (best[1].name, best[0][0])


def _clip_spans(trace, spans):
    a, b = trace.window
    return [s for s in spans if s.end > a and s.start < b]


def idle_gaps(trace, top: int = 10):
    """The ``top`` longest idle gaps, each ``[name, seconds]``."""
    spans = _spans(trace)
    if spans is None:
        return None
    spans = _clip_spans(trace, spans)
    gaps = sorted(trace.gaps(), key=lambda g: g[0] - g[1])[:top]
    return [[_name_gap(spans, a, b)[0], b - a] for a, b in gaps]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(gaps, intervals) -> float:
    """Seconds of ``gaps`` that lie inside the union of ``intervals``."""
    cover = _union(intervals)
    total, j = 0.0, 0
    for a, b in gaps:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def idle_by_span(trace) -> dict | None:
    """The window's idle seconds by the name of their gaps, by gap size, and
    the share of them that lies inside some program span."""
    spans = _spans(trace)
    if spans is None:
        return None
    spans = _clip_spans(trace, spans)
    gaps = trace.gaps()
    by_name, by_size = collections.Counter(), collections.Counter()
    for a, b in gaps:
        by_name[_name_gap(spans, a, b)[0]] += b - a
        by_size[next(label for lim, label in GAP_SIZES if b - a < lim)] += b - a
    idle = sum(b - a for a, b in gaps)
    inside = _covered(gaps, [(s.start, s.end) for s in spans])
    return {"idle_s": idle,
            "in_spans_share": inside / idle if idle > 0 else None,
            "by_span": dict(by_name.most_common()),
            "by_gap_size": {label: by_size[label] for _, label in GAP_SIZES}}


def scope(event) -> str | None:
    """The innermost ``ug.*`` scope in an op event's stats, if any."""
    for v in event.stats.values():
        if isinstance(v, str) and "ug." in v:
            found = _SCOPE.findall(v)
            if found:
                return found[-1]
    return None


def device_ops(trace, top: int = 15):
    """Device time by op inside the window (``.N`` suffixes merged), each op
    named ``<scope>:<op>`` where its event carries a ``ug.*`` scope."""
    a, b = trace.window
    ops = collections.Counter()
    for x in trace.ops:
        s, e = max(x.start, a), min(x.end, b)
        if e > s:
            name = re.sub(r"(\.\d+)+$", "", x.name)
            sc = scope(x)
            ops[f"{sc}:{name}" if sc else name] += e - s
    return [[k, v] for k, v in ops.most_common(top)]


def op_events_per_s(trace) -> float | None:
    """Device op events that start inside the window, per second."""
    a, b = trace.window
    if b <= a:
        return None
    return sum(a <= x.start < b for x in trace.ops) / (b - a)


def _starting(trace, spans, name):
    a, b = trace.window
    return [s for s in spans if s.name == name and a <= s.start < b]


def _median(xs):
    xs = sorted(xs)
    if not xs:
        return None
    m = len(xs) // 2
    return xs[m] if len(xs) % 2 else (xs[m - 1] + xs[m]) / 2


def idle_preparing_share(trace) -> float | None:
    """Percent of the window in which no device op runs while the dispatcher
    is inside ``serve.pack`` or ``serve.launch``."""
    spans = _spans(trace)
    if spans is None or trace.window_s <= 0:
        return None
    prep = [(s.start, s.end) for s in spans if s.name in ("serve.pack", "serve.launch")]
    return 100.0 * _covered(trace.gaps(), prep) / trace.window_s


def serve_pack_ms(trace) -> float | None:
    """Median ``serve.pack`` duration, in ms, over batches that start in the
    window."""
    spans = _spans(trace)
    if spans is None:
        return None
    m = _median([s.dur for s in _starting(trace, spans, "serve.pack")])
    return None if m is None else 1e3 * m


def search_launch_ms(trace) -> float | None:
    """Median ``serve.launch`` duration, in ms, over the same batches."""
    spans = _spans(trace)
    if spans is None:
        return None
    batches = {s.stats.get("batch") for s in _starting(trace, spans, "serve.pack")}
    m = _median([s.dur for s in spans
                 if s.name == "serve.launch" and s.stats.get("batch") in batches])
    return None if m is None else 1e3 * m


def beam_slot_use(trace) -> float | None:
    """Expansions done over expansion slots run, in percent, over the
    ``serve.resolve`` spans that start in the window: the sum of
    ``steps_sum`` over the sum of ``iters * width * bucket``."""
    spans = _spans(trace)
    if spans is None:
        return None
    rs = _starting(trace, spans, "serve.resolve")
    slots = sum(s.stats["iters"] * s.stats["width"] * s.stats["bucket"] for s in rs)
    return 100.0 * sum(s.stats["steps_sum"] for s in rs) / slots if slots else None
