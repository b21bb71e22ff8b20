"""PDE workloads over the unified solver framework.

The paper's two case studies (``heat1d``, ``swe2d``) plus beyond-paper
scenario workloads (``heat2d``, ``advection1d``, ``burgers1d``, and
``swe_sphere``: Williamson's shallow water on the sphere), each a
:class:`~repro.pde.solver.Stepper` registered by name. Generic code drives
them through :class:`~repro.pde.solver.Simulation`::

    from repro.pde import Simulation, known_steppers
    res = Simulation("burgers1d", None, PRESETS["r2f2_16"]).run(1000)

The original per-workload entry points (``simulate_heat``/``simulate_swe``,
``heat_step``/``swe_step``) remain as numerics-identical shims.
"""

from .registry import get_stepper, known_steppers, register_stepper
from .solver import SimResult, Simulation, StepOps, Stepper

from .advection1d import AdvectionConfig, initial_profile
from .burgers1d import BurgersConfig, initial_wave
from .heat1d import HeatConfig, heat_step
from .heat1d import simulate as simulate_heat
from .heat2d import Heat2DConfig, initial_condition_2d
from .precision_ops import padd, pdiv, pmul, pstore
from .scenarios import Scenario, measure, observe, scenarios
from .swe2d import SWEConfig, swe_step
from .swe2d import simulate as simulate_swe
from .swe_sphere import SphereConfig

__all__ = [
    # framework
    "Stepper",
    "StepOps",
    "Simulation",
    "SimResult",
    "register_stepper",
    "get_stepper",
    "known_steppers",
    # the paper-pattern verdict, one scenario per stepper
    "Scenario",
    "scenarios",
    "observe",
    "measure",
    # workload configs + shims
    "HeatConfig",
    "Heat2DConfig",
    "AdvectionConfig",
    "BurgersConfig",
    "SWEConfig",
    "SphereConfig",
    "initial_condition_2d",
    "initial_profile",
    "initial_wave",
    "heat_step",
    "swe_step",
    "simulate_heat",
    "simulate_swe",
    "pmul",
    "pstore",
    "pdiv",
    "padd",
]
