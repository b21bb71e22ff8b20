"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.

Single pod:  (16, 16)      -> ("data", "model")        = 256 chips
Multi-pod:   (2, 16, 16)   -> ("pod", "data", "model") = 512 chips

The 'pod' axis carries outer data parallelism / FSDP; cross-pod traffic is
gradient reduction only (and optional rr-16-compressed, train.py
--grad-comm), matching DCI << ICI bandwidth reality.

Every axis is ``Auto``: the program states shardings through
``with_sharding_constraint`` (``repro.dist.sharding.constrain``) and leaves
their propagation to the compiler. ``jax.make_mesh`` would otherwise make
``Explicit`` axes, on which such constraints are refused.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """A mesh of ``Auto`` axes over ``devices`` (default: all devices)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples): 1D data mesh."""
    return make_mesh((len(jax.devices()),), ("data",))
