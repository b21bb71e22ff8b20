"""The program's own ``service.*`` host spans in this run's trace.

``SimService`` writes a ``jax.profiler`` annotation at each boundary of its
host path (``repro.obs.span``, DESIGN.md §15). The readers of the
service's per-layer metrics take them from the ``.xplane.pb`` that
``jax.profiler`` wrote for this run, the newest under ``harness.TRACE_DIR``:

    win = window(ctx)   # a Window, or None

The file is accepted only if its ``bench.window`` span is as long as the
window that ``bench.reduce`` read (``ctx.trace.window_s``). A file not
accepted, or one with no ``service.*`` span in the window (a program that
writes none), gives None, and so does every reader. The last parse is kept
for the next reader of the same run.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import math
import os
import statistics
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import harness, reduce

PREFIX = "service."


class HostSpan(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, object]

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-6


@dataclasses.dataclass
class Window:
    """The ``service.*`` spans that lie wholly inside the window, by name
    and in start order, and the device operations (``XLA Ops`` events)
    that start in it, per device."""

    spans: Dict[str, List[HostSpan]]
    device_ops: float

    def named(self, name: str) -> List[HostSpan]:
        return self.spans.get(name, [])

    def children(self, parent: HostSpan, name: str) -> List[HostSpan]:
        """The spans called ``name`` that lie inside ``parent``."""
        named = self.named(name)
        lo = bisect.bisect_left(named, parent.start_ns, key=lambda s: s.start_ns)
        hi = bisect.bisect_left(named, parent.end_ns, key=lambda s: s.start_ns)
        return [s for s in named[lo:hi] if s.end_ns <= parent.end_ns]


def median(values) -> Optional[float]:
    values = list(values)
    return statistics.median(values) if values else None


def parse(profile) -> Tuple[float, Window]:
    """The window's length in seconds and its :class:`Window`."""
    host = [
        HostSpan(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in profile.planes if plane.name == reduce.HOST_PLANE
        for line in plane.lines
        for ev in line.events
        if ev.name == reduce.WINDOW_SPAN or ev.name.startswith(PREFIX)
    ]
    windows = [s for s in host if s.name == reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {reduce.WINDOW_SPAN!r} host span")
    w = max(windows, key=lambda s: s.end_ns - s.start_ns)
    inside: Dict[str, List[HostSpan]] = defaultdict(list)
    for s in sorted(host, key=lambda s: s.start_ns):
        if s.name.startswith(PREFIX) and w.start_ns <= s.start_ns and s.end_ns <= w.end_ns:
            inside[s.name].append(s)
    ops, n_devices = 0, 0
    for plane in profile.planes:
        if not plane.name.startswith(reduce.DEVICE_PREFIX):
            continue
        n_devices += 1
        ops += sum(
            w.start_ns <= ev.start_ns < w.end_ns
            for line in plane.lines if line.name == reduce.OPS_LINE
            for ev in line.events
        )
    return (w.end_ns - w.start_ns) * 1e-9, Window(dict(inside), ops / max(n_devices, 1))


@functools.lru_cache(maxsize=1)
def _parse_file(path: str, mtime_ns: int, size: int) -> Tuple[float, Window]:
    from jax.profiler import ProfileData

    return parse(ProfileData.from_file(path))


def window(ctx) -> Optional[Window]:
    try:
        path = reduce.find_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    st = os.stat(path)
    window_s, win = _parse_file(path, st.st_mtime_ns, st.st_size)
    if not math.isclose(window_s, ctx.trace.window_s, rel_tol=1e-9) or not win.spans:
        return None
    return win
