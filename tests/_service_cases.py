"""Shared cases and reference runs of the ``repro.service`` tests.

Not a test module: ``test_service.py``, ``test_service_packing.py`` and
``test_service_resident.py`` import its stepper grids, precision modes,
cached solo runs and assertion helpers.
"""

import dataclasses
import functools

import jax
import numpy as np

from repro.core.policy import PRESETS
from repro.pde import (
    AdvectionConfig,
    BurgersConfig,
    HeatConfig,
    Heat2DConfig,
    SWEConfig,
    Simulation,
)


TRACKED = dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")

#: small grids: the parity matrix runs 5 steppers x 6 modes in the fast tier
SMALL_CFGS = {
    "heat1d": HeatConfig(nx=48),
    "heat2d": Heat2DConfig(nx=16, ny=16),
    "advection1d": AdvectionConfig(nx=64),
    "burgers1d": BurgersConfig(nx=48),
    "swe2d": SWEConfig(nx=16, ny=16),
}

#: (label, config, bit_exact) — rr_tracked's guarantee is final split k +
#: §5.3 counters (bit-exactness additionally holds on the reference plane
#: and is asserted there)
MODES = (
    ("f32", PRESETS["f32"], True),
    ("bf16", PRESETS["bf16"], True),
    ("e5m10", PRESETS["e5m10"], True),
    ("r2f2_16", PRESETS["r2f2_16"], True),
    ("deploy", PRESETS["deploy"], True),
    ("rr_tracked", TRACKED, True),
)


def _scaled(state, s):
    return jax.tree_util.tree_map(lambda x: (s * x).astype(x.dtype), state)


@functools.lru_cache(maxsize=None)
def _solo(stepper, mode, steps, every, scale=None):
    """The solo reference run of a request (shared by the tests that serve it)."""
    cfg = SMALL_CFGS[stepper]
    sim = Simulation(stepper, cfg, dict((m[0], m[1]) for m in MODES)[mode])
    state0 = None if scale is None else _scaled(sim.stepper.init_state(cfg), scale)
    return sim.run(steps, snapshot_every=every, state0=state0)


def _assert_trackers_equal(a, b):
    assert (a is None) == (b is None)
    if a is None:
        return
    np.testing.assert_array_equal(np.asarray(a.state.k), np.asarray(b.state.k))
    np.testing.assert_array_equal(
        np.asarray(a.state.overflow_steps), np.asarray(b.state.overflow_steps)
    )
    np.testing.assert_array_equal(
        np.asarray(a.state.shrink_steps), np.asarray(b.state.shrink_steps)
    )


def _fail_second_chunk(svc, key):
    """Make the chunk program of bucket key ``key`` raise on its second call."""
    get, calls = svc._compiler.get, []

    def wrapped(sim, k, *args, **kw):
        fn, fresh = get(sim, k, *args, **kw)
        if k != key:
            return fn, fresh

        def chunk(*xs):
            calls.append(k)
            if len(calls) == 2:
                raise RuntimeError("injected chunk failure")
            return fn(*xs)

        return chunk, fresh

    svc._compiler.get = wrapped


def _assert_tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), a, b
    )
