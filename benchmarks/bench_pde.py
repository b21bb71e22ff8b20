"""Generic per-stepper PDE benchmark — every registered solver workload
through the same precision ladder, on ALL execution planes.

One scenario per registered stepper (``repro.pde.known_steppers``): run the
f32 reference, then each precision in the ladder under
``execution="reference"`` (the stepwise StepOps engine path),
``execution="fused"`` (whole snapshot intervals as Pallas kernel chunks)
AND ``execution="megakernel"`` (the entire horizon in ONE pallas_call,
DESIGN.md §14), reporting per-step wall time, the paper's correctness
verdict (relative L2 for decaying fields, field correlation for the SWE
basin), static op counts of one snapshot-chunk program (``pallas`` =
pallas_call count — the fused plane collapses a chunk into one; ``hlo`` =
lowered instruction count), the whole-horizon launch count (``launches`` =
scan-weighted pallas_call count of the full run's program: ``steps/every``
for the chunked plane, exactly 1 for the megakernel — asserted, that IS
the tentpole claim), and the §5.3 adjustment counters
(``adj=+grow/-shrink``) for tracked runs. ``main`` fails loudly if a
registered stepper has no scenario, so adding a workload without
benchmarking it is impossible.

CSV rows: ``pde/<case>/<prec>/<exec>,us_per_step,rel=..;corr=..;STATUS;...``
— captured by ``benchmarks.run`` into ``BENCH_pde.json``. ``--smoke`` (or
``main(smoke=True)``) caps step counts for the CI fast tier, so the bench
trajectory accumulates on every push.

Storage pairing: for the rr precisions in :data:`PACKED_PRECS`, every fused
row gets a paired ``fused+packed`` row — the same chunked program carrying
R2F2-packed state (``storage="packed"``) between chunk boundaries instead
of f32 — and both report ``bytes_per_step`` (2x the carried-state footprint:
one read + one write per step at the storage boundary,
``repro.pack.state_nbytes``). The packed row's bytes must come in under the
f32 row's — that IS the bandwidth claim, regression-checked per push.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional, Tuple

import numpy as np

from repro.pack import is_packed, state_nbytes, unpack_state
from repro.precision import PRESETS
from repro.pde import Simulation, get_stepper, known_steppers

DEFAULT_PRECS = ("e5m10", "r2f2_16", "r2f2_15", "bf16", "rr_tracked")
#: rr precisions whose fused rows get a paired ``fused+packed`` storage row
PACKED_PRECS = ("r2f2_16", "rr_tracked")
SMOKE_STEPS = 60

#: the bench ladder's precision configs: the PRESETS plus the tracked rr
#: mode (the adjustment-counter story needs a carried tracker)
PREC_LADDER = dict(
    PRESETS,
    rr_tracked=dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked"),
)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One benchmarked configuration of a registered stepper."""

    cfg: Any
    steps: int
    precs: Tuple[str, ...] = DEFAULT_PRECS
    judge: str = "rel"  # "rel": rel_l2 < 0.1 | "corr": field corr > 0.98
    offset: float = 0.0  # constant background removed before the metrics
    label: Optional[str] = None


def scenarios():
    """Scenario table, keyed by stepper name (configs/* are the source of
    figure-faithful shapes/steps)."""
    from repro.configs import advection1d, burgers1d, heat1d, heat2d, swe2d, williamson5

    return {
        "heat1d": Scenario(heat1d.CONFIG, heat1d.BENCH_STEPS["sin"]),
        "heat2d": Scenario(heat2d.CONFIG, heat2d.BENCH_STEPS),
        "advection1d": Scenario(advection1d.CONFIG, advection1d.BENCH_STEPS),
        "burgers1d": Scenario(burgers1d.CONFIG, burgers1d.BENCH_STEPS),
        "swe2d": Scenario(
            swe2d.CONFIG,
            swe2d.BENCH_STEPS,
            precs=("e5m10", "r2f2_16", "r2f2_16_384", "bf16", "rr_tracked"),
            judge="corr",
            offset=swe2d.CONFIG.depth,
        ),
        "swe_sphere": Scenario(
            williamson5.CONFIG,
            williamson5.BENCH_STEPS,
            precs=("e5m10", "r2f2_16", "r2f2_16_384", "bf16"),
            offset=williamson5.CONFIG.h0,
        ),
    }


def observe(stepper, cfg, state, offset: float = 0.0):
    """A run's observable as a metrics-ready array (background removed)."""
    return np.asarray(stepper.observables(state, cfg)) - offset


def measure(out, ref, judge: str = "rel"):
    """The suite's single verdict logic: finite / rel L2 / corr / correct.

    Shared with examples/pde_zoo.py so the zoo's printout and
    BENCH_pde.json can never disagree about a workload.
    """
    finite = bool(np.isfinite(out).all())
    if finite:
        rel = float(np.linalg.norm(out - ref) / np.linalg.norm(ref))
        corr = float(np.corrcoef(out.reshape(-1), ref.reshape(-1))[0, 1])
    else:
        rel, corr = float("nan"), float("nan")
    ok = finite and (corr > 0.98 if judge == "corr" else rel < 0.1)
    return dict(rel=rel, corr=corr, finite=finite, correct=ok)


def _iter_subjaxprs(v):
    vals = v if isinstance(v, (list, tuple)) else (v,)
    for w in vals:
        inner = getattr(w, "jaxpr", w)
        if hasattr(inner, "eqns"):
            yield inner


def _count_pallas_weighted(jaxpr) -> int:
    """pallas_call count with scan trip counts multiplied through — i.e.
    the number of kernel LAUNCHES the program issues at runtime, not the
    number of call sites in the jaxpr text."""
    n = 0
    for eqn in jaxpr.eqns:
        w = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in _iter_subjaxprs(v):
                n += w * _count_pallas_weighted(sub)
    return n


def chunk_op_counts(sim: Simulation, chunk: int, execution: str, storage: str = "f32"):
    """Static op counts of one snapshot-chunk program: (pallas_calls,
    lowered instruction count). The fused plane's signature is one
    pallas_call per chunk where the reference plane scans per-step engine
    ops."""
    import jax

    state0 = sim.stepper.init_state(sim.cfg)

    def fn(s0):
        return sim.run(
            chunk, snapshot_every=chunk, state0=s0, execution=execution,
            storage=storage,
        ).state

    traced = jax.jit(fn).trace(state0)  # one trace serves both counts
    n_pallas = _count_pallas_weighted(traced.jaxpr.jaxpr)
    lowered = traced.lower().as_text()
    n_hlo = sum(1 for line in lowered.splitlines() if " = " in line)
    return n_pallas, n_hlo


def horizon_launches(
    sim: Simulation, steps: int, every: int, execution: str, storage: str = "f32"
) -> int:
    """Kernel launches of the FULL horizon program (scan-weighted
    pallas_call count): ``steps/every`` chunks on the fused plane, 0 on the
    reference plane, and — the whole point — exactly 1 on the megakernel
    plane, snapshots and remainder included."""
    import jax

    state0 = sim.stepper.init_state(sim.cfg)

    def fn(s0):
        return sim.run(
            steps, snapshot_every=every, state0=s0, execution=execution,
            storage=storage,
        ).state

    return _count_pallas_weighted(jax.jit(fn).trace(state0).jaxpr.jaxpr)


def run_case(name: str, sc: Scenario, smoke: bool = False):
    """f32 reference + precision ladder, reference-vs-fused paired rows."""
    stepper = get_stepper(name)
    cfg = sc.cfg
    steps = min(sc.steps, SMOKE_STEPS) if smoke else sc.steps
    chunk = max(1, steps // stepper.snapshots_default)
    ref = observe(
        stepper, cfg, Simulation(name, cfg, PRESETS["f32"]).run(steps).state, sc.offset
    )
    rows = []
    for prec_name in sc.precs:
        prec = PREC_LADDER[prec_name]
        # chunked-vs-mega paired rows: every fused row gets a megakernel
        # partner (same storage), so launches/bytes/us compare side by side
        storages = [("reference", "f32"), ("fused", "f32"), ("megakernel", "f32")]
        if prec_name in PACKED_PRECS:
            storages.append(("fused", "packed"))  # the bandwidth pair row
            storages.append(("megakernel", "packed"))
        for execution, storage in storages:
            sim = Simulation(name, cfg, prec)
            if execution == "fused" and not sim.fused_eligible():
                continue  # mode/stepper outside the fused plane: no pair row
            if execution == "megakernel" and not sim.mega_eligible():
                continue  # outside the megakernel plane: no pair row
            t0 = time.perf_counter()
            res = sim.run(steps, execution=execution, storage=storage)
            state = res.state
            out_state = unpack_state(state) if is_packed(state) else state
            out = observe(stepper, cfg, out_state, sc.offset)
            us = (time.perf_counter() - t0) * 1e6 / steps
            n_pallas, n_hlo = chunk_op_counts(sim, chunk, execution, storage)
            launches = horizon_launches(sim, steps, chunk, execution, storage)
            if execution == "megakernel" and launches != 1:
                raise SystemExit(
                    f"megakernel row {name}/{prec_name}/{storage} issued "
                    f"{launches} kernel launches for the horizon; the "
                    "whole-horizon contract is exactly 1"
                )
            row = dict(
                case=sc.label or name,
                prec=prec_name,
                execution=execution if storage == "f32" else f"{execution}+{storage}",
                us_per_step=us,
                pallas_calls=n_pallas,
                hlo_ops=n_hlo,
                launches=launches,
                # one read + one write of the carried state per step
                bytes_per_step=2 * state_nbytes(state),
                **measure(out, ref, sc.judge),
            )
            if res.tracker is not None:  # §5.3 adjustment counters
                row["grow_adjusts"] = int(np.asarray(res.tracker.state.overflow_steps).sum())
                row["shrink_adjusts"] = int(np.asarray(res.tracker.state.shrink_steps).sum())
            rows.append(row)
    return rows


def format_row(r, suite: str = "pde") -> str:
    status = (
        "DESTROYED(NaN)"
        if not r["finite"]
        else ("CORRECT" if r["correct"] else "WRONG")
    )
    derived = (
        f"rel={r['rel']:.4f};corr={r['corr']:.4f};{status};"
        f"pallas={r['pallas_calls']};hlo={r['hlo_ops']}"
        f";launches={r['launches']}"
        f";bytes_per_step={r['bytes_per_step']}"
    )
    if "grow_adjusts" in r:
        derived += f";adj=+{r['grow_adjusts']}/-{r['shrink_adjusts']}"
    return f"{suite}/{r['case']}/{r['prec']}/{r['execution']},{r['us_per_step']:.1f},{derived}"


def main(smoke: bool = False):
    table = scenarios()
    missing = [s for s in known_steppers() if s not in table]
    if missing:
        raise SystemExit(f"steppers without a bench scenario: {missing}")
    print("# per-stepper precision ladder x execution plane:")
    print("# E5M10 fails its way, R2F2-16 matches f32; fused == reference in 1 pallas_call/chunk")
    for name in known_steppers():
        sc = table[name]
        st = get_stepper(name)
        print(f"# {name} [{st.failure_mode}] {st.story}")
        for r in run_case(name, sc, smoke=smoke):
            print(format_row(r))


if __name__ == "__main__":
    import sys

    main(smoke="--smoke" in sys.argv)
