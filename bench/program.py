"""How the benchmark reaches the system under test: its configurations,
precisions and seeds. Nothing here computes a result."""

from __future__ import annotations

import dataclasses

import numpy as np


def program_config(cfg: dict):
    """The stepper's own config object with the configuration's fields."""
    from repro.pde import get_stepper

    base = get_stepper(cfg["stepper"]).default_config()
    return dataclasses.replace(base, **cfg["fields"])


def precision(spec: dict):
    """A ``PrecisionConfig`` from a mix's precision entry: a preset, with its
    ``mode`` and flexible format ``fmt`` ([EB, MB, FX]) replaced if given."""
    from repro.core.flexformat import FlexFormat
    from repro.core.policy import PRESETS

    prec = PRESETS[spec.get("preset", "f32")]
    changes = {}
    if "mode" in spec:
        changes["mode"] = spec["mode"]
    if "fmt" in spec:
        changes["fmt"] = FlexFormat(*spec["fmt"])
    return dataclasses.replace(prec, **changes)


def seed_key(seed: int):
    """A JAX key from any whole seed below 2**62 (wider than 32 bits)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def seed_rng(seed: int, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng([seed % 2**32, seed // 2**32, salt])
