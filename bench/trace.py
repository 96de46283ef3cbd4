"""From a profiler trace to the numbers the per-layer metrics read.

A traced run wraps its measured window in the host span ``bench.window``
and each call into the program in a ``bench.<what>`` span
(``jax.profiler.TraceAnnotation``). The device planes (``/device:TPU:<i>``)
hold one event per operation that ran; the line that holds them is named in
:data:`DEVICE_LINES`. Host and device events share the trace's clock.

- busy time: the union of a device's operation intervals inside the
  window, averaged over the devices the cell uses;
- idle gaps: the complement of that union inside the window, each named by
  the innermost ``bench.`` host span that covers its middle;
- device ops: total device time per operation name.

The reduction works on plain ``Event`` tuples, so a small recorded trace
(``bench/tests/data``) checks it without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# the line of a device plane that holds one event per executed operation
DEVICE_LINES = ("XLA Ops",)


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(log_dir: str) -> List[Event]:
    """Every event of the ``.xplane.pb`` that ``jax.profiler.trace`` wrote
    under ``log_dir``, from device planes and the host's CPU plane."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not (plane.name.startswith("/device:")
                or plane.name == "/host:CPU"):
            continue
        for line in plane.lines:
            if plane.name == "/host:CPU" or line.name in DEVICE_LINES:
                events.extend(Event(plane.name, line.name, e.name,
                                    float(e.start_ns), float(e.duration_ns))
                              for e in line.events)
    return events


def save_events(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the devices used
    device_ops: List[Tuple[str, float]]  # (name, seconds), longest first
    idle_gaps: List[Tuple[str, float]]   # the ``top`` longest, longest first


def reduce(events: List[Event], n_devices: int, top: int = 10) -> Summary:
    """Busy time, idle gaps and op totals inside the ``bench.window`` span,
    on the first ``n_devices`` device planes."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    planes = sorted({e.plane for e in events
                     if e.plane.startswith("/device:")},
                    key=lambda p: int(re.sub(r"\D", "", p) or 0))[:n_devices]
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    inside = [e for e in events if e.plane in planes
              and e.end_ns > w0 and e.start_ns < w1]
    busy, per_op = [], {}
    for plane in planes:
        spans = _union([(max(e.start_ns, w0), min(e.end_ns, w1))
                        for e in inside if e.plane == plane])
        busy.append(sum(e - s for s, e in spans))
        if plane == planes[0]:
            gaps, t = [], w0
            for s, e in spans:
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
            if t < w1:
                gaps.append((t, w1))
    for e in inside:
        per_op[e.name] = per_op.get(e.name, 0.0) + e.dur_ns
    host = [e for e in events if e.plane == "/host:CPU"
            and e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN]

    def doing(s: float, e: float) -> str:
        mid = (s + e) / 2
        cover = [h for h in host if h.start_ns <= mid < h.end_ns]
        return min(cover, key=lambda h: h.dur_ns).name if cover else "no span"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    idle = [(doing(s, e), (e - s) * 1e-9) for s, e in longest]
    ops = sorted(((k, v * 1e-9) for k, v in per_op.items()),
                 key=lambda x: -x[1])
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   device_ops=ops, idle_gaps=idle)


def breakdown(summary: Optional[Summary], top: int = 10) -> Dict[str, list]:
    if summary is None:
        return {}
    return {"device_ops": [list(x) for x in summary.device_ops[:top]],
            "idle_gaps": [list(x) for x in summary.idle_gaps[:top]]}
