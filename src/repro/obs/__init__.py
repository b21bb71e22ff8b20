"""repro.obs — unified observability for the service, solver and kernel planes.

One subsystem the whole stack reports into (DESIGN.md §15):

* :mod:`repro.obs.trace` — nestable spans + lifecycle instants with a
  process-wide sampled recorder; Chrome-trace/Perfetto JSON export.
* :mod:`repro.obs.metrics` — named counters/gauges/histograms with label
  sets; Prometheus text + JSON export, strict round-trip parser.
* :mod:`repro.obs.precision` — per-site carried-k time series, §5.3
  grow/shrink counters and evidence-coverage fractions, drained from
  trackers at chunk boundaries.
* :mod:`repro.obs.timing` — the shared bench helper with an explicit
  compile/execute split.
* :mod:`repro.obs.health` — the live monitoring plane over this substrate
  (DESIGN.md §16): anomaly detectors on the telemetry stream, shadow-
  oracle sampling (:mod:`repro.obs.shadow`), declarative SLO rules, a
  bounded flight recorder (:mod:`repro.obs.flightrec`) and a stdlib-HTTP
  scrape endpoint (:mod:`repro.obs.server`).
* ``python -m repro.obs`` — headless fleet reporter over exported
  artifacts, plus the ``--smoke`` self-check CI gates on; ``python -m
  repro.obs.health`` is the health-plane counterpart (offline detector
  replay, ``--watch``, and its own ``--smoke`` gate).

The passivity contract
----------------------

Instrumentation is **passive**: it observes values the program already
materialises on the host and never feeds anything back.

* Spans and metrics are host-side Python; nothing here is traced into a
  jitted program, so an instrumented run is bit-identical to an
  uninstrumented one (proven by ``tests/test_obs.py``'s parity suite).
* Telemetry drains only *concrete* trackers: :func:`record_tracker`
  refuses jax tracers, so instrumented code inside ``jit``/``vmap``
  quietly skips the drain instead of corrupting the trace.
* :func:`span` and :func:`instant` always write to the profiler sink, a
  ``jax.profiler.TraceAnnotation`` (about 1 us when no profiler is
  running), so a ``jax.profiler`` trace carries them on the device
  trace's clock with obs enabled or not. The in-memory :class:`Tracer`
  records them only when observability is enabled; disabled (the
  default), the counters short-circuit before any lookup.

Usage::

    import repro.obs as obs

    obs.enable(sample=1.0)
    ... run / serve ...
    paths = obs.export("artifacts/obs")   # trace.json, metrics.prom, ...
    obs.disable()
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from .metrics import (  # noqa: F401  (re-exported)
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_prometheus,
)
from .precision import PrecisionTelemetry, load_telemetry  # noqa: F401
from .timing import Timing, measure  # noqa: F401
from .trace import Span, Tracer, load_trace  # noqa: F401

__all__ = [
    "Observability",
    "enable",
    "disable",
    "active",
    "enabled",
    "span",
    "instant",
    "inc",
    "observe",
    "set_gauge",
    "record_tracker",
    "export",
    # re-exports
    "Tracer",
    "Span",
    "load_trace",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "parse_prometheus",
    "PrecisionTelemetry",
    "load_telemetry",
    "Timing",
    "measure",
]


class Observability:
    """One enabled observability scope: a tracer, a metrics registry and a
    precision-telemetry accumulator."""

    def __init__(
        self,
        trace: bool = True,
        telemetry: bool = True,
        sample: float = 1.0,
        capacity: int = 65536,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.tracer: Optional[Tracer] = (
            Tracer(sample=sample, capacity=capacity) if trace else None
        )
        self.registry: MetricsRegistry = registry or MetricsRegistry()
        self.telemetry: Optional[PrecisionTelemetry] = (
            PrecisionTelemetry() if telemetry else None
        )

    def export(self, out_dir: str) -> Dict[str, str]:
        """Write every artifact under ``out_dir``; returns name -> path."""
        os.makedirs(out_dir, exist_ok=True)
        paths: Dict[str, str] = {}
        if self.tracer is not None:
            paths["trace"] = self.tracer.save(os.path.join(out_dir, "trace.json"))
        prom = os.path.join(out_dir, "metrics.prom")
        mjson = os.path.join(out_dir, "metrics.json")
        self.registry.save(prom_path=prom, json_path=mjson)
        paths["prometheus"] = prom
        paths["metrics_json"] = mjson
        if self.telemetry is not None:
            paths["telemetry"] = self.telemetry.save(
                os.path.join(out_dir, "telemetry.json")
            )
        return paths


_OBS: Optional[Observability] = None


def enable(
    trace: bool = True,
    telemetry: bool = True,
    sample: float = 1.0,
    capacity: int = 65536,
    registry: Optional[MetricsRegistry] = None,
) -> Observability:
    """Turn on process-wide observability (idempotent: replaces any prior
    scope). ``sample`` thins top-level spans deterministically."""
    global _OBS
    _OBS = Observability(
        trace=trace,
        telemetry=telemetry,
        sample=sample,
        capacity=capacity,
        registry=registry,
    )
    return _OBS


def disable() -> None:
    """Turn observability off; every hook reverts to its no-op fast path."""
    global _OBS
    _OBS = None


def active() -> Optional[Observability]:
    """The enabled scope, or None."""
    return _OBS


def enabled() -> bool:
    return _OBS is not None


# ---------------------------------------------------------------------------
# instrumentation hooks — the Tracer, registry and telemetry record only
# after enable(); spans also reach a running jax.profiler trace
# ---------------------------------------------------------------------------

#: ``jax.profiler.TraceAnnotation``, imported on first use: the reporter
#: (``python -m repro.obs``) reads artifacts without jax installed
_annotation = None


def _note(name: str, args: Dict[str, Any]):
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation as _annotation
    return _annotation(name, **args)


class _Span:
    """A span on both sinks: a profiler annotation and a :class:`Tracer`
    record. Like the annotation alone, it yields itself, and its
    ``set_metadata`` attaches args known only inside the span."""

    __slots__ = ("_note", "_rec", "_args")

    def __init__(self, name: str, args: Dict[str, Any], tracer: Tracer):
        self._note = _note(name, args)
        self._rec = tracer.span(name, **args)
        self._args: Optional[Dict[str, Any]] = None

    def __enter__(self) -> "_Span":
        self._note.__enter__()
        self._args = self._rec.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._rec.__exit__(*exc)
        self._note.__exit__(*exc)
        return False

    def set_metadata(self, **args) -> None:
        self._note.set_metadata(**args)
        self._args.update(args)


def span(name: str, **args):
    """A span context manager. It always enters a
    ``jax.profiler.TraceAnnotation(name, **args)``, which a running
    ``jax.profiler`` trace records on the device trace's clock; with
    observability enabled the :class:`Tracer` records it too. The ``with``
    target's ``set_metadata(**args)`` attaches late args to both.

    Arg values reach the profiler as text or numbers; a text value must
    hold no ``,`` or ``#``, which the profiler's metadata encoding uses.
    """
    o = _OBS
    if o is None or o.tracer is None:
        return _note(name, args)
    return _Span(name, args, o.tracer)


def instant(name: str, **args) -> None:
    """A zero-duration lifecycle event, on both sinks like :func:`span`."""
    note = _note(name, args)
    note.__enter__()
    note.__exit__(None, None, None)
    o = _OBS
    if o is not None and o.tracer is not None:
        o.tracer.instant(name, **args)


def inc(name: str, amount: float = 1, help: str = "", **labels) -> None:
    """Bump a counter on the active registry."""
    o = _OBS
    if o is not None:
        o.registry.counter(name, help).inc(amount, **labels)


def observe(name: str, value: float, help: str = "", **labels) -> None:
    """Record a histogram observation on the active registry."""
    o = _OBS
    if o is not None:
        o.registry.histogram(name, help).observe(value, **labels)


def set_gauge(name: str, value: float, help: str = "", **labels) -> None:
    """Set a gauge on the active registry."""
    o = _OBS
    if o is not None:
        o.registry.gauge(name, help).set(value, **labels)


def _concrete(tracker) -> bool:
    """True iff every leaf of the tracker is a concrete (non-traced) value —
    the guard that keeps telemetry drains out of jit/vmap traces."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tracker):
        if isinstance(leaf, jax.core.Tracer):
            return False
    return True


def record_tracker(scope: str, tracker, step: int) -> None:
    """Drain a carried SiteTracker's (k, grow, shrink) into the telemetry
    series at ``step``. No-op when disabled, when ``tracker`` is None, or —
    crucially — when called under a jax trace (passivity: the drain never
    enters a jitted program)."""
    o = _OBS
    if o is None or o.telemetry is None or tracker is None:
        return
    if not _concrete(tracker):
        return
    o.telemetry.record_tracker(scope, tracker, step)


def export(out_dir: str) -> Dict[str, str]:
    """Export the active scope's artifacts (raises if disabled)."""
    if _OBS is None:
        raise RuntimeError("repro.obs is not enabled; call repro.obs.enable() first")
    return _OBS.export(out_dir)
