"""The plain references against the program's own ``execution="reference"``
plane, in float32 at small sizes."""

import dataclasses

import jax
import numpy as np
import pytest

from bench import compare, harness, program
from bench.reference import heat1d, swe2d


def _program(config, fields, states, steps, every):
    from repro.core.policy import PRESETS
    from repro.pde import Simulation

    cfg = dataclasses.replace(program.program_config(config), **fields)
    sim = Simulation(config["stepper"], cfg, PRESETS["f32"])
    res = sim.run_ensemble(states, steps, snapshot_every=every, execution="reference")
    return np.asarray(res.state), np.asarray(res.snapshots)


@pytest.mark.parametrize("scales", [[1.0], [0.5, 1.5]])
def test_heat1d_reference_matches_program(scales):
    config = harness.load_json("configs", "heat1d_128")
    fields = dict(config["fields"], nx=32)
    states = heat1d.initial_state(fields, np.array(scales))
    final, snaps = _program(config, {"nx": 32}, states, 200, 50)
    ref_final, ref_snaps = jax.vmap(lambda s: heat1d.run(fields, s, 200, 50))(states)
    gaps = compare.worst_member_gap(final, snaps, ref_final, ref_snaps, heat1d.offsets(fields))
    assert gaps.max() <= 1e-6


def test_heat1d_initial_state_is_the_programs():
    config = harness.load_json("configs", "heat1d_128")
    pcfg = program.program_config(config)
    from repro.pde import get_stepper

    mine = heat1d.initial_state(config["fields"], np.array([1.0]))[0]
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(get_stepper("heat1d").init_state(pcfg)))


@pytest.mark.parametrize("scales", [[1.0], [0.5, 1.5]])
def test_swe2d_reference_matches_program(scales):
    config = harness.load_json("configs", "swe2d_128")
    fields = dict(config["fields"], nx=16, ny=16)
    states = swe2d.initial_state(fields, np.array(scales))
    final, snaps = _program(config, {"nx": 16, "ny": 16}, states, 40, 10)
    ref_final, ref_snaps = jax.vmap(lambda s: swe2d.run(fields, s, 40, 10))(states)
    gaps = compare.worst_member_gap(final, snaps, ref_final, ref_snaps, swe2d.offsets(fields))
    # the two round in different orders (0.5 g (h h) against (0.5 g h) h): a
    # float32 ulp of h (500 m) is about 5e-7 of the 100 m wave, and such
    # differences add up over the 40 steps
    assert gaps.max() <= 1e-4


def test_gap_of_a_non_finite_field_is_infinite():
    assert compare.rel_l2([1.0, float("nan")], [1.0, 2.0]) == float("inf")
    assert compare.rel_l2([2.0, 2.0], [1.0, 1.0]) == pytest.approx(1.0)
    assert compare.rel_l2([501.0], [502.0], offset=500.0) == pytest.approx(0.5)
