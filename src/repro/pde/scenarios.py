"""The paper-pattern verdict: one scenario per registered stepper.

Each :class:`Scenario` names a stepper's configured shape and horizon
(``repro.configs``), the constant background its observable is judged
about, and the verdict that applies: relative L2 for decaying fields, field
correlation for the SWE basin. :func:`measure` is that verdict, shared by
``chip_smoke.py`` and ``examples/pde_zoo.py`` so the two can never disagree
about a workload.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

__all__ = ["Scenario", "scenarios", "observe", "measure"]


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One judged configuration of a registered stepper."""

    cfg: Any
    steps: int
    judge: str = "rel"  # "rel": rel_l2 < 0.1 | "corr": field corr > 0.98
    offset: float = 0.0  # constant background removed before the metrics


def scenarios():
    """Scenario table, keyed by stepper name (configs/* are the source of
    figure-faithful shapes/steps)."""
    from repro.configs import advection1d, burgers1d, heat1d, heat2d, swe2d, williamson5

    return {
        "heat1d": Scenario(heat1d.CONFIG, heat1d.BENCH_STEPS),
        "heat2d": Scenario(heat2d.CONFIG, heat2d.BENCH_STEPS),
        "advection1d": Scenario(advection1d.CONFIG, advection1d.BENCH_STEPS),
        "burgers1d": Scenario(burgers1d.CONFIG, burgers1d.BENCH_STEPS),
        "swe2d": Scenario(
            swe2d.CONFIG, swe2d.BENCH_STEPS, judge="corr", offset=swe2d.CONFIG.depth
        ),
        "swe_sphere": Scenario(
            williamson5.CONFIG, williamson5.BENCH_STEPS, offset=williamson5.CONFIG.h0
        ),
    }


def observe(stepper, cfg, state, offset: float = 0.0):
    """A run's observable as a metrics-ready array (background removed)."""
    return np.asarray(stepper.observables(state, cfg)) - offset


def measure(out, ref, judge: str = "rel"):
    """The single verdict logic: finite / rel L2 / corr / correct."""
    finite = bool(np.isfinite(out).all())
    if finite:
        rel = float(np.linalg.norm(out - ref) / np.linalg.norm(ref))
        corr = float(np.corrcoef(out.reshape(-1), ref.reshape(-1))[0, 1])
    else:
        rel, corr = float("nan"), float("nan")
    ok = finite and (corr > 0.98 if judge == "corr" else rel < 0.1)
    return dict(rel=rel, corr=corr, finite=finite, correct=ok)
