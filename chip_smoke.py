#!/usr/bin/env python3
"""On-chip smoke test of the main path, through the entry points a user calls.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # sharded ensemble over a 4-chip host

One chip, three phases, all in this one process:

* ``planes`` — ``Simulation.run`` of heat1d, burgers1d and swe2d at their
  configured sizes and horizons (``repro.configs``) under f32, E5M10,
  R2F2-16 and rr_tracked, on the reference, chunked-fused and megakernel
  execution planes, each named explicitly. Each result is judged against
  the f32 reference plane with the paper-pattern verdict
  (``repro.pde.measure``): E5M10 must fail, R2F2-16 and
  rr_tracked must match f32. Each kernel plane is also compared with the
  reference plane of the same mode on the same chip.
* ``ensemble`` — ``run_ensemble`` of 64 swe2d members on the megakernel
  plane; members are checked against solo runs of the same initial states.
* ``service`` — a mixed ``SimService`` burst that must drain with every
  request done and every snapshot finite.

``--chips 4`` runs only the sharded ensemble: the same 64 members over a
four-device mesh and on one device, compared member for member.

Every phase prints its compile seconds and whether its lowered program holds
a Pallas TPU kernel (``tpu_custom_call``). The last line of standard output
is ``{"ok": true, "device": {...}}``. Without a TPU, or when any phase
fails, the script exits nonzero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PLANE_STEPPERS = ("heat1d", "burgers1d", "swe2d")
MODES = ("f32", "e5m10", "r2f2_16", "rr_tracked")
PLANES = ("reference", "fused", "megakernel")
#: the paper's verdicts: a 16-bit fixed format fails, R2F2-16 matches f32
EXPECT_CORRECT = {"f32": True, "e5m10": False, "r2f2_16": True, "rr_tracked": True}
ENSEMBLE_MEMBERS = 64


class PhaseFailed(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def precision(mode: str):
    import dataclasses

    from repro.core.policy import PRESETS

    if mode == "rr_tracked":
        return dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")
    return PRESETS[mode]


def compile_program(fn, *args):
    """Lower and compile ``jax.jit(fn)``; returns (compiled, seconds, has_kernel)."""
    import jax

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t0, has_kernel


def run_compiled(compiled, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def max_abs_diff(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both = np.isfinite(a) & np.isfinite(b)
    if not np.array_equal(np.isfinite(a), np.isfinite(b)):
        return float("inf")
    return float(np.max(np.abs(a[both] - b[both]), initial=0.0))


def phase_planes(steppers=PLANE_STEPPERS, steps=None, cfgs=None):
    """Every (stepper, mode, plane) through ``Simulation.run``."""
    import numpy as np

    from repro.pde import Simulation, measure, observe, scenarios

    table = scenarios()
    failures = []
    for name in steppers:
        sc = table[name]
        cfg = sc.cfg if cfgs is None else cfgs[name]
        n = sc.steps if steps is None else steps[name]
        ref_obs = None
        for mode in MODES:
            results = {}
            for plane in PLANES:
                sim = Simulation(name, cfg, precision(mode))
                state0 = sim.stepper.init_state(cfg)

                def fn(s0, sim=sim, plane=plane):
                    res = sim.run(n, state0=s0, execution=plane)
                    k = None if res.tracker is None else res.tracker.state.k
                    return res.state, k

                compiled, c_s, has_kernel = compile_program(fn, state0)
                (state, k), r_s = run_compiled(compiled, state0)
                out = observe(sim.stepper, cfg, state, sc.offset)
                if mode == "f32" and plane == "reference":
                    ref_obs = out
                v = measure(out, ref_obs, sc.judge)
                results[plane] = out
                kernel_ok = has_kernel == (plane != "reference")
                verdict_ok = v["correct"] == EXPECT_CORRECT[mode]
                diff = "" if plane == "reference" else (
                    f" max|plane-reference|={max_abs_diff(out, results['reference'])!r}"
                )
                log(
                    f"[planes] {name}/{mode}/{plane} steps={n} compile_s={c_s:.2f} "
                    f"run_s={r_s:.3f} tpu_custom_call={has_kernel} "
                    f"rel={v['rel']:.4g} corr={v['corr']:.6f} "
                    f"{'CORRECT' if v['correct'] else 'WRONG'}"
                    f"{'' if k is None else f' k={np.asarray(k).tolist()}'}{diff}"
                )
                if not kernel_ok:
                    failures.append(f"{name}/{mode}/{plane}: tpu_custom_call={has_kernel}")
                if not verdict_ok:
                    failures.append(
                        f"{name}/{mode}/{plane}: expected "
                        f"{'CORRECT' if EXPECT_CORRECT[mode] else 'WRONG'}"
                    )
    if failures:
        raise PhaseFailed("; ".join(failures))


def ensemble_batch(n_members: int, cfg=None):
    """``n_members`` swe2d initial states with bump heights spread over
    0.5x..1.5x of the configured bump (the resting depth is kept)."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import swe2d
    from repro.pde.swe2d import initial_state

    cfg = swe2d.CONFIG if cfg is None else cfg
    scales = [0.5 + i / max(1, n_members - 1) for i in range(n_members)]
    return jnp.stack(
        [initial_state(dataclasses.replace(cfg, bump=cfg.bump * s)) for s in scales]
    ), cfg


def phase_ensemble(n_members=ENSEMBLE_MEMBERS, steps=None, cfg=None, mode="rr_tracked"):
    """One ``run_ensemble`` of swe2d members on the megakernel plane,
    checked against solo runs of its first and last member."""
    import numpy as np

    from repro.configs import swe2d
    from repro.pde import Simulation

    batch, cfg = ensemble_batch(n_members, cfg)
    n = swe2d.BENCH_STEPS if steps is None else steps
    sim = Simulation("swe2d", cfg, precision(mode))

    def ens(b):
        return sim.run_ensemble(b, n, execution="megakernel").state

    compiled, c_s, has_kernel = compile_program(ens, batch)
    out, r_s = run_compiled(compiled, batch)
    out = np.asarray(out)
    log(
        f"[ensemble] swe2d/{mode}/megakernel members={n_members} steps={n} "
        f"compile_s={c_s:.2f} run_s={r_s:.3f} tpu_custom_call={has_kernel} "
        f"shape={out.shape} finite={bool(np.isfinite(out).all())}"
    )

    def solo(s0):
        return sim.run(n, state0=s0, execution="megakernel").state

    solo_c, c_s, _ = compile_program(solo, batch[0])
    worst = 0.0
    for i in (0, n_members - 1):
        ref, _ = run_compiled(solo_c, batch[i])
        d = max_abs_diff(out[i], ref)
        worst = max(worst, d)
        log(f"[ensemble] member {i}: max|member-solo|={d!r} (solo compile_s={c_s:.2f})")
    if not has_kernel or out.shape[0] != n_members or not np.isfinite(out).all():
        raise PhaseFailed("ensemble: missing kernel, wrong shape or non-finite members")
    if worst != 0.0:
        raise PhaseFailed(f"ensemble members differ from solo runs by {worst!r}")


def phase_service(steps=None):
    """A mixed burst through ``SimService`` that must drain cleanly."""
    import numpy as np

    from repro.service import ServiceConfig, SimRequest, SimService, scaled_state0

    steps = {"heat1d": 400, "burgers1d": 240, "swe2d": 80} if steps is None else steps
    svc = SimService(ServiceConfig(max_bucket=8, max_queue=256))
    handles = []
    for name, n in steps.items():
        for mode in ("f32", "r2f2_16", "rr_tracked"):
            for i, execution in enumerate(("fused", "megakernel", "fused", "megakernel")):
                handles.append(
                    svc.submit(
                        SimRequest(
                            name,
                            steps=n,
                            precision=mode,
                            execution=execution,
                            state0=scaled_state0(name, 0.6 + 0.2 * i),
                            tag=f"{name}/{mode}/{execution}#{i}",
                        )
                    )
                )
    t0 = time.perf_counter()
    svc.run_until_idle()
    wall = time.perf_counter() - t0
    m = svc.metrics
    bad = []
    for h in handles:
        if h.status != "done":
            bad.append(f"{h.tag}: {h.status}")
            continue
        if not all(np.isfinite(np.asarray(s)).all() for s in h.result().snapshots):
            bad.append(f"{h.tag}: non-finite snapshot")
    log(
        f"[service] requests={len(handles)} done={len(handles) - len(bad)} "
        f"wall_s={wall:.2f} compile_s={m.compile_seconds:.2f} compiles={m.compiles}"
    )
    if bad:
        raise PhaseFailed("service: " + "; ".join(bad))


def phase_sharded(n_chips: int, n_members=ENSEMBLE_MEMBERS, steps=None, cfg=None):
    """The same swe2d members sharded over ``n_chips`` devices and on one
    device, compared member for member."""
    import jax
    import numpy as np

    from repro.configs import swe2d
    from repro.dist.sharding import axis_rules
    from repro.launch.mesh import make_mesh
    from repro.pde import Simulation

    devices = jax.devices()[:n_chips]
    batch, cfg = ensemble_batch(n_members, cfg)
    n = swe2d.BENCH_STEPS if steps is None else steps
    sim = Simulation("swe2d", cfg, precision("rr_tracked"))
    mesh = make_mesh((n_chips,), ("data",), devices=devices)

    def sharded(b):
        with axis_rules(mesh):
            return sim.run_ensemble(b, n, sharded=True, execution="megakernel").state

    def single(b):
        return sim.run_ensemble(b, n, execution="megakernel").state

    with mesh:
        c_sh, c_s, has_kernel = compile_program(sharded, batch)
        out_sh, r_s = run_compiled(c_sh, batch)
    placed = sorted(
        (d.id, int(s.data.shape[0]))
        for s in out_sh.addressable_shards
        for d in [s.device]
    )
    gathers = c_sh.as_text().count("all-gather")
    log(
        f"[sharded] swe2d/rr_tracked/megakernel members={n_members} steps={n} "
        f"devices={n_chips} compile_s={c_s:.2f} run_s={r_s:.3f} "
        f"tpu_custom_call={has_kernel} all-gathers={gathers} "
        f"members_per_device={placed}"
    )
    one = jax.device_put(batch, devices[0])
    c_one, c_s, _ = compile_program(single, one)
    out_one, r_s = run_compiled(c_one, one)
    log(f"[sharded] one device: compile_s={c_s:.2f} run_s={r_s:.3f}")
    a, b = np.asarray(out_sh), np.asarray(out_one)
    diffs = [max_abs_diff(a[i], b[i]) for i in range(n_members)]
    log(f"[sharded] max|sharded-one_device| over members={max(diffs)!r}")
    if not has_kernel or len({d for d, _ in placed}) != n_chips:
        raise PhaseFailed(f"sharded: members not on all {n_chips} devices: {placed}")
    if max(diffs) != 0.0:
        raise PhaseFailed("sharded members differ from the one-device run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded ensemble over a 4-chip host")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, {len(devices)} found",
              file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache

    log(f"[setup] device={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={enable_compile_cache()}")
    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(4))]
    else:
        phases = [
            ("planes", phase_planes),
            ("ensemble", phase_ensemble),
            ("service", phase_service),
        ]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception as e:  # every phase runs; any failure fails the script
            failed.append(name)
            traceback.print_exc()
            log(f"[{name}] FAILED: {type(e).__name__}: {e}")
        log(f"[{name}] phase_s={time.perf_counter() - t0:.2f}")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
