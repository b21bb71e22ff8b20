"""Closed loop of back-to-back ensemble horizons.

Each horizon is one call of ``Simulation.run_ensemble`` over ``members``
initial states that the call itself builds on the device from ``(seed,
horizon index)``: the reference module's ``initial_state`` at a scale per
member drawn uniformly from ``scale`` (swe2d scales the bump's height). So
no horizon repeats another.
The next horizon is dispatched when the last one's result is ready. With
more than one device the members are sharded over a one-axis mesh
(``run_ensemble(sharded=True)`` inside ``dist.sharding.axis_rules``).

Mix keys: ``members``, ``scale`` ([lo, hi]), ``precision`` and ``control``
(see ``bench.program.precision``), ``execution``, ``sample`` (how many
horizons the check compares, drawn uniformly from the window's by a
reservoir seeded from the seed; the last whole horizon is compared too) and
``limit`` (of ``rel_l2_max``).
"""

from __future__ import annotations

import contextlib
import importlib
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import compare, program


class Run:
    def __init__(self, *, config, mix, seed, seconds, devices, control=False):
        self.config, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.devices, self.control = list(devices), control
        self.fields = config["fields"]
        self.steps, self.every = config["steps"], config["snapshot_every"]
        self.members = mix["members"]
        self.ref = importlib.import_module(f"bench.reference.{config['stepper']}")
        self.counts: dict = {}
        self.attempted = self.failed = 0

    # -- set-up -------------------------------------------------------------

    def _states(self, h):
        lo, hi = self.mix["scale"]
        key = jax.random.fold_in(program.seed_key(self.seed), h)
        scales = lo + (hi - lo) * jax.random.uniform(key, (self.members,))
        return self.ref.initial_state(self.fields, scales)

    def _context(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.dist.sharding import axis_rules

        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(axis_rules(self.mesh))
        return stack

    def setup(self):
        from repro.pde import Simulation

        prec = program.precision(self.mix["control" if self.control else "precision"])
        sim = Simulation(self.config["stepper"], program.program_config(self.config), prec)
        self.mesh = None
        if len(self.devices) > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            from repro.launch.mesh import make_mesh

            self.mesh = make_mesh((len(self.devices),), ("data",), devices=self.devices)
            members = NamedSharding(self.mesh, PartitionSpec("data"))

        def horizon(h):
            s0 = self._states(h)
            if self.mesh is not None:
                s0 = jax.lax.with_sharding_constraint(s0, members)
            res = sim.run_ensemble(
                s0, self.steps, snapshot_every=self.every,
                execution=self.mix["execution"], sharded=self.mesh is not None,
            )
            return res.state, res.snapshots

        with self._context():
            self.fn = jax.jit(horizon).lower(np.int32(0)).compile()
            jax.block_until_ready(self.fn(np.int32(0)))

    # -- the measured window ---------------------------------------------------

    def window(self):
        draw, size = program.seed_rng(self.seed, 1), self.mix["sample"]
        reservoir = []
        h = 1
        with self._context():
            t0 = time.perf_counter()
            while True:
                with TraceAnnotation("bench.dispatch"):
                    out = self.fn(np.int32(h))
                with TraceAnnotation("bench.wait"):
                    jax.block_until_ready(out)
                t = time.perf_counter()
                if len(reservoir) < size:
                    reservoir.append((h, out))
                else:
                    j = int(draw.integers(h))  # horizons 1..h seen so far
                    if j < size:
                        reservoir[j] = (h, out)
                last = h, out
                h += 1
                if t - t0 >= self.seconds:
                    break
        self.kept = dict(reservoir)
        self.kept[last[0]] = last[1]
        horizons = h - 1
        self.attempted = horizons * self.members
        self.counts = dict(
            horizons=horizons,
            members=self.members,
            member_steps=horizons * self.members * self.steps,
            member_horizons=horizons * self.members,
            window_s=t - t0,
        )
        return self.counts

    def end_to_end(self):
        return {"member_steps_per_s": self.counts["member_steps"] / self.counts["window_s"]}

    def free(self):
        del self.fn

    # -- correctness -------------------------------------------------------------

    def check(self):
        steps, every = self.steps, self.every
        gen = jax.jit(self._states)
        ref = jax.jit(jax.vmap(lambda s: self.ref.run(self.fields, s, steps, every)))
        offsets = self.ref.offsets(self.fields)
        worst, compared = 0.0, 0
        for h in sorted(self.kept):
            final, snaps = (np.asarray(x) for x in self.kept.pop(h))
            ref_final, ref_snaps = ref(gen(np.int32(h)))
            gaps = compare.worst_member_gap(final, snaps, ref_final, ref_snaps, offsets)
            worst = max(worst, float(gaps.max()))
            compared += len(gaps)
        self.counts["compared"] = compared
        return [("rel_l2_max", worst, self.mix["limit"])]
