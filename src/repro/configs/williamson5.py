"""Williamson et al. (1992) test case 5: zonal flow over an isolated
mountain, shallow water on the sphere at T42-class resolution.

128 x 64 cells of 2.8125 degrees, dt = 8 s (a model day is 10,800 steps).
Only the lambda-face zonal momentum flux runs on the configured multiplier
and divider; ``hu`` (about 1.2e5) overflows E5M10 as an operand and ``hu*hu``
(about 1.4e10) needs R2F2-16 ``<3,8,4>``'s widest split E7M8.
"""

from repro.pde.swe_sphere import SphereConfig

CONFIG = SphereConfig(nlon=128, nlat=64, dt=8.0)
STEPS_PER_DAY = 10_800
BENCH_STEPS = STEPS_PER_DAY
