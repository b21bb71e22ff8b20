"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics read.

    trace = reduce(load(trace_dir))

reads the ``.xplane.pb`` that ``jax.profiler`` wrote under ``trace_dir`` and
returns a :class:`Trace`:

* ``window_s`` — the length of the benchmark's ``bench.window`` host span,
  the traced window;
* ``busy_s`` — the union of the intervals in which an operation ran on a
  device, clipped to the window and averaged over the devices;
* ``ops`` — device seconds per operation name, summed over devices;
* ``idle`` — the idle time of every device inside the window, split over
  the benchmark's ``bench.*`` host spans that overlap each gap (what the
  host was doing meanwhile), averaged over the devices; ``bench.window``
  keeps what no inner span covers.

Device operations are the events of each device plane's ``XLA Ops`` line.
On a TPU such an event is named by its whole HLO instruction text; an
operation's name here is the instruction's name, the text before `` = ``
(for example ``%vmap_jit_swe2d_mega__.1``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float
    n_devices: int
    ops: Dict[str, float]
    idle: Dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.idle.items(), key=lambda kv: -kv[1])[:n]

    def op_seconds(self, match) -> float:
        """Device seconds of every operation whose name ``match`` accepts."""
        return sum(s for name, s in self.ops.items() if match(name))


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(trace_dir))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _host_spans(profile) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return spans


def _attribute(gs: float, ge: float, inner, starts, idle) -> None:
    """Split the idle gap ``[gs, ge]`` over the inner host spans it overlaps
    (sorted by start, not overlapping one another); what none covers goes to
    the window itself."""
    covered = 0.0
    k = max(0, bisect.bisect_right(starts, gs) - 1)
    while k < len(inner) and inner[k][0] < ge:
        s, e, name = inner[k]
        overlap = min(e, ge) - max(s, gs)
        if overlap > 0:
            idle[name] += overlap * 1e-9
            covered += overlap
        k += 1
    if ge - gs > covered:
        idle[WINDOW_SPAN] += (ge - gs - covered) * 1e-9


def reduce(profile) -> Trace:
    spans = _host_spans(profile)
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    inner = sorted(sp for sp in spans if sp[2] != WINDOW_SPAN)
    starts = [sp[0] for sp in inner]
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy, n_devices = 0.0, 0
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    intervals.append((s, e))
                    ops[op_name(ev.name)] += (e - s) * 1e-9
        n_devices += 1
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                _attribute(gs, ge, inner, starts, idle)
    if n_devices == 0:
        raise ValueError(f"the trace has no {DEVICE_PREFIX}* plane")
    return Trace(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy * 1e-9 / n_devices,
        n_devices=n_devices,
        ops=dict(ops),
        idle={k: v / n_devices for k, v in idle.items()},
    )
