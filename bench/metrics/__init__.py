"""Per-layer metric readers, one module per metric, named as the metric.

A reader defines ``read(ctx)`` and returns the metric's value, or None when
the run holds nothing for it to read. ``ctx`` carries ``trace`` (a
``bench.reduce.Trace``), ``counts`` (the traffic driver's counts of the
window), ``work`` (the configuration's ``bench.work`` module), ``config``
and ``peaks`` (the device's row of ``bench.peaks``).
"""

import re

# The megakernel's pallas_call has no name of its own: its operation takes
# the name of the jitted wrapper around it (``jit_swe2d_mega``,
# ``jit_heat1d_mega``; ``vmap_`` in front when members are batched).
MEGAKERNEL = re.compile(r"jit_\w+_mega(_|\.|$)")


def is_megakernel(op: str) -> bool:
    return MEGAKERNEL.search(op) is not None
