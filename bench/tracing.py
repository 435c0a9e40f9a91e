"""Profiler trace of part of the window, and its reduction to numbers.

The trace is taken with ``jax.profiler`` in a run of its own (``--trace
1``).  The bench brackets the traced span with its own host span
(:data:`WINDOW`), so the window's bounds are on the trace's clock.  From
the device planes it reads: the union of the intervals in which an
operation ran (busy time), each kernel's time and launches, and the idle
gaps between operations, each named by the bench's host span that
overlaps it most.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW = "bench.trace_window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
# A TPU op event is named by its HLO instruction's text,
# ``%expand_score.7 = f32[256,384]{..} custom-call(...)``: the name is the
# instruction's, and the text is kept for its shapes.
_HLO = re.compile(r"%?([^\s=%]+) = ")


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds on the trace's clock
    end: float
    stats: dict = dataclasses.field(default_factory=dict)
    device: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """What the reduction needs: the device ops and the traced window."""
    ops: list
    window: tuple

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clip(self, evs):
        a, b = self.window
        return [(max(e.start, a), min(e.end, b)) for e in evs
                if e.end > a and e.start < b]

    def busy_intervals(self, device=None):
        """Merged intervals in which a device op ran inside the window, on
        ``device`` or on any device."""
        out = []
        evs = [e for e in self.ops if device is None or e.device == device]
        for s, e in sorted(self._clip(evs)):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices traced."""
        devices = sorted({e.device for e in self.ops})
        return sum(e - s for d in devices for s, e in self.busy_intervals(d)) / max(
            len(devices), 1)

    def idle_share(self) -> float | None:
        if self.window_s <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def kernel(self, name: str):
        """Device events of one kernel: its name, or its name with an
        ``.N`` suffix, as the compiler names repeated call sites."""
        pat = re.compile(re.escape(name) + r"(\.\d+)*$")
        return [e for e in self.ops if pat.match(e.name)
                and self.window[0] <= e.start < self.window[1]]

    def gaps(self):
        """Idle gaps inside the window, as ``(start, end)``."""
        a, b = self.window
        out, t = [], a
        for s, e in self.busy_intervals():
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if b > t:
            out.append((t, b))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by name, ``.N`` suffixes
        merged) and the longest idle gaps, none of them attributed."""
        ops = collections.Counter()
        for s, e, name in ((max(x.start, self.window[0]), min(x.end, self.window[1]),
                            x.name) for x in self.ops):
            if e > s:
                ops[re.sub(r"(\.\d+)+$", "", name)] += e - s
        gaps = sorted((e - s for s, e in self.gaps()), reverse=True)[:top]
        return {"device_ops": [[k, v] for k, v in ops.most_common(top)],
                "idle_gaps": [["unattributed", g] for g in gaps]}


def device_event(name: str, start: float, end: float, stats: dict,
                 device: str) -> Event:
    """A device op, named by its HLO instruction where the trace gives the
    instruction's text, which is then kept under ``hlo_text``."""
    m = _HLO.match(name)
    if m:
        name, stats = m.group(1), {**stats, "hlo_text": name}
    return Event(name, start, end, stats, device)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, window = [], []
    for plane in pd.planes:
        on_device = re.fullmatch(re.escape(DEVICE_PLANE) + r"\d+", plane.name)
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not on_device and ev.name != WINDOW:
                    continue
                start, end = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                if on_device:
                    ops.append(device_event(ev.name, start, end, dict(ev.stats),
                                            plane.name))
                else:
                    window.append((start, end))
    if not window:
        raise ValueError(f"trace has no {WINDOW} span")
    return Trace(ops, window[0])
