"""The paper's own workload #1: 1D heat equation (explicit FD).

Figure-faithful configuration (see EXPERIMENTS.md §Claims rows 6 & 8):
physical diffusivity drives the alpha*lap products below E5M10's subnormal
floor late in the simulation — the paper's underflow failure mode.
"""

from repro.pde.heat1d import HeatConfig

CONFIG = HeatConfig(nx=128, init="sin", alpha=1e-5, cfl=0.4, amplitude=500.0, modes=3)
BENCH_STEPS = 4000
