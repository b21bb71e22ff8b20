"""String-keyed stepper registry — the pluggability point of the PDE surface.

Mirrors :mod:`repro.precision.registry`: every scenario workload registers a
:class:`repro.pde.solver.Stepper` under a short name, and everything generic
— the :class:`~repro.pde.solver.Simulation` driver, ``examples/pde_zoo.py``,
the README scenario table — iterates
:func:`known_steppers` instead of hard-coding workload modules. A third-party
stepper (a reaction-diffusion system, a wave equation, ...) becomes a named
scenario the moment it calls :func:`register_stepper`, with zero edits
elsewhere.

This module deliberately imports nothing from :mod:`repro.pde.solver` at
module scope, so workload modules can import it while the package is still
mid-initialisation.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Dict, Tuple

if TYPE_CHECKING:  # pragma: no cover - type-only
    from repro.pde.solver import Stepper

__all__ = ["register_stepper", "get_stepper", "known_steppers"]

_STEPPERS: Dict[str, "Stepper"] = {}
_builtins_loaded = False


def register_stepper(name: str, stepper=None):
    """Register ``stepper`` (an instance or a class) under ``name``.

    Usable directly (``register_stepper("wave1d", Wave1DStepper())``) or as a
    class decorator (``@register_stepper("wave1d")``). Re-registering a name
    replaces the previous stepper — deliberate, so tests/experiments can
    shadow a builtin. Returns the stepper/class for decorator chaining.
    """
    if stepper is None:
        return lambda s: register_stepper(name, s)
    instance = stepper() if isinstance(stepper, type) else stepper
    instance.name = name
    _STEPPERS[name] = instance
    return stepper


def _load_builtins() -> None:
    global _builtins_loaded
    if not _builtins_loaded:
        # registering happens at module import; workload modules are listed
        # here (not via the package __init__) to avoid an import cycle
        from repro.pde import (  # noqa: F401
            advection1d, burgers1d, heat1d, heat2d, swe2d, swe_sphere,
        )

        # flag set only on success so a failed import is retried, not masked
        _builtins_loaded = True


def get_stepper(name: str) -> "Stepper":
    """Resolve a stepper name to its registered instance."""
    _load_builtins()
    try:
        return _STEPPERS[name]
    except KeyError:
        raise KeyError(
            f"no PDE stepper registered for {name!r}; known: {known_steppers()}"
        ) from None


def known_steppers() -> Tuple[str, ...]:
    """All currently registered stepper names."""
    _load_builtins()
    return tuple(sorted(_STEPPERS))
