"""The registered PDE scenario zoo, one precision ladder each.

    PYTHONPATH=src python examples/pde_zoo.py [--steppers a,b] [--ensemble N]
                                              [--execution reference|fused|megakernel|auto]

Drives every workload through the shared ``repro.pde.solver.Simulation``
(no per-workload code): f32 reference, the failing E5M10 baseline, 16-bit
R2F2, and a *tracked* R2F2 run whose final per-site splits are printed —
the paper's precision-adjust unit carried across the whole simulation.
Scenario shapes/steps/metric offsets and the verdict come from
``repro.pde.scenarios``, the table ``chip_smoke.py`` judges with. With
``--ensemble N``, each scenario also runs a vmapped N-member ensemble of
scaled initial conditions (add a sharding mesh via dist.sharding to spread
it over devices).

Fused quickstart (DESIGN.md §10): ``--execution fused`` runs every ladder
entry as multi-substep Pallas kernel chunks — same verdicts, one
``pallas_call`` per snapshot interval, tracked splits folded from the
kernels' range evidence::

    PYTHONPATH=src python examples/pde_zoo.py --execution fused --steppers burgers1d

Megakernel quickstart (DESIGN.md §14): ``--execution megakernel`` runs each
entry's ENTIRE horizon — snapshots and the on-chip adjust unit included —
in exactly one ``pallas_call``, bit-identical to the fused plane::

    PYTHONPATH=src python examples/pde_zoo.py --execution megakernel --steppers burgers1d

Profiling quickstart (DESIGN.md §11): ``--profile`` additionally captures
each scenario's range distributions on the f32 run and prints the
``repro.profile`` RangeReport (per-site dynamic range, exponent spread over
time, coverage at each flexible split) plus the splits the policy
autotuner would deploy::

    PYTHONPATH=src python examples/pde_zoo.py --profile --steppers heat1d
"""

import argparse
import dataclasses

import numpy as np

from repro.precision import PRESETS
from repro.pde import (
    Scenario,
    Simulation,
    get_stepper,
    known_steppers,
    measure,
    observe,
    scenarios,
)

TRACKED = dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steppers", default=None, help="comma-separated subset")
    ap.add_argument("--ensemble", type=int, default=0, help="vmapped ensemble size")
    ap.add_argument(
        "--execution",
        default="reference",
        choices=("reference", "fused", "megakernel", "auto"),
        help="arithmetic plane: stepwise engines, Pallas kernel chunks, the "
        "whole-horizon megakernel, or auto (prefers megakernel)",
    )
    ap.add_argument(
        "--profile",
        action="store_true",
        help="capture range distributions on the f32 run and print the "
        "repro.profile report + autotuned splits",
    )
    args = ap.parse_args()
    names = args.steppers.split(",") if args.steppers else known_steppers()
    table = scenarios()

    for name in names:
        stepper = get_stepper(name)
        # steppers registered outside the scenario table still run, on defaults
        sc = table.get(name) or Scenario(cfg=stepper.default_config(), steps=400)
        print(f"\n=== {name} [{stepper.failure_mode}] — {stepper.story}"
              f" (execution={args.execution})")
        ref = None
        for prec_name, prec in (
            ("f32", PRESETS["f32"]),
            ("e5m10", PRESETS["e5m10"]),
            ("r2f2_16", PRESETS["r2f2_16"]),
            ("rr_tracked", TRACKED),
        ):
            sim = Simulation(name, sc.cfg, prec)
            res = sim.run(sc.steps, execution=args.execution)
            obs = observe(stepper, sim.cfg, res.state, sc.offset)
            if ref is None:
                ref = obs
                print(f"  {prec_name:11s} reference |max|={np.abs(ref).max():.4g}")
                continue
            m = measure(obs, ref, sc.judge)
            if not m["finite"]:
                print(f"  {prec_name:11s} DESTROYED (NaN/inf)")
                continue
            verdict = "" if m["correct"] else "  [WRONG]"
            line = f"  {prec_name:11s} rel L2 {m['rel']:.5f}"
            if sc.judge == "corr":  # show the number the verdict judges
                line += f" corr {m['corr']:.4f}"
            line += verdict
            if res.tracker is not None:
                ks = {n: int(res.tracker.k(n)) for n in res.tracker.names}
                line += f"   final splits {ks}"
            print(line)

        if args.profile:
            from repro.profile import capture_profile, synthesize_policy

            profile, _ = capture_profile(
                name, sc.cfg, steps=sc.steps, execution=args.execution
                if args.execution != "auto" else "reference",
            )
            print("  " + profile.report().summary().replace("\n", "\n  "))
            pol = synthesize_policy(profile)
            print("  autotuned splits: "
                  + ", ".join(f"{n}: k={d['k']} [{d['k_lo']},{d['k_hi']}]"
                              for n, d in pol.sites.items()))

        if args.ensemble:
            sim = Simulation(name, sc.cfg, PRESETS["r2f2_16"])
            u0 = sim.stepper.init_state(sim.cfg)
            scales = np.linspace(0.5, 1.5, args.ensemble, dtype=np.float32)
            u0b = scales.reshape((-1,) + (1,) * u0.ndim) * np.asarray(u0)[None]
            ens = sim.run_ensemble(u0b, max(1, sc.steps // 4), execution=args.execution)
            print(f"  ensemble[{args.ensemble}] state {ens.state.shape} "
                  f"finite={bool(np.isfinite(np.asarray(ens.state)).all())}")


if __name__ == "__main__":
    main()
