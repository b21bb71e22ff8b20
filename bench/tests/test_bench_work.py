"""bench.work counts against the plain references' own operations."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.reference import heat1d, swe2d

ARITH = {"add", "sub", "mul", "div"}


def _count_interior_ops(fn, x) -> int:
    """Elementwise float32 arithmetic in ``fn``'s jaxpr, weighted by the
    elements each operation makes; operations on a wall strip (a dim of
    extent 1) are boundary copies, not the update."""
    total = 0
    for eqn in jax.make_jaxpr(fn)(x).jaxpr.eqns:
        if eqn.primitive.name in ARITH:
            shape = eqn.outvars[0].aval.shape
            if 1 not in shape:
                n = 1
                for d in shape:
                    n *= d
                total += n
    return total


@pytest.mark.parametrize("nx,ny", [(8, 8), (16, 12), (128, 128)])
def test_swe2d_step_flops_match_reference(nx, ny):
    work = harness.load_module("work", "swe2d_128")
    cfg = dict(harness.load_json("configs", "swe2d_128")["fields"], nx=nx, ny=ny)
    U = jnp.ones((3, nx, ny), jnp.float32)
    assert _count_interior_ops(lambda u: swe2d.step(u, cfg), U) == work.step_flops(cfg)


@pytest.mark.parametrize("nx", [8, 128])
def test_heat1d_step_flops_match_reference(nx):
    work = harness.load_module("work", "heat1d_128")
    cfg = dict(harness.load_json("configs", "heat1d_128")["fields"], nx=nx)
    u = jnp.ones((nx,), jnp.float32)
    assert _count_interior_ops(lambda v: heat1d.step(v, cfg), u) == work.step_flops(cfg)


def test_horizon_work_and_least_bytes():
    swe = harness.load_json("configs", "swe2d_128")
    work = harness.load_module("work", "swe2d_128")
    assert work.flops(swe) == 400 * work.step_flops(swe["fields"])
    # state (3 fields) read once, 4 snapshots of h and the final state written
    assert work.hbm_bytes(swe) == (3 + 4 + 3) * 128 * 128 * 4
    heat = harness.load_json("configs", "heat1d_128")
    work = harness.load_module("work", "heat1d_128")
    assert work.flops(heat) == 4000 * 6 * 126
    assert work.hbm_bytes(heat) == (1 + 8 + 1) * 128 * 4
