"""Shared test config: property-test backend selection + example budgets.

The bit-level property modules (test_flexformat, test_r2f2, test_alu,
test_pack) are written against the hypothesis API. The baked runtime image
does not ship hypothesis and the repo installs nothing, so when the real
package is absent we install ``tests/_hypothesis_stub.py`` (same API
surface: kwargs-``given``, ``settings``, ``floats``/``integers``
strategies; deterministic, edge-first, bounded) as ``sys.modules
["hypothesis"]`` before collection. Either way the per-test example count
is capped by ``REPRO_HYPOTHESIS_EXAMPLES`` (default 50) so the CI fast
tier's property pass stays inside its time budget; set it higher locally
for a deeper sweep.
"""

import glob
import os
import sys
from typing import Any, Dict, NamedTuple

import pytest

collect_ignore = []

try:
    import hypothesis

    _BUDGET = int(os.environ.get("REPRO_HYPOTHESIS_EXAMPLES", "50"))
    hypothesis.settings.register_profile(
        "repro_ci", max_examples=_BUDGET, deadline=None
    )
    hypothesis.settings.load_profile("repro_ci")
except ImportError:
    import importlib.util

    _path = os.path.join(os.path.dirname(__file__), "_hypothesis_stub.py")
    _spec = importlib.util.spec_from_file_location("hypothesis", _path)
    _stub = importlib.util.module_from_spec(_spec)
    sys.modules["hypothesis"] = _stub
    _spec.loader.exec_module(_stub)


class HostSpan(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, Any]


@pytest.fixture
def profiled(tmp_path):
    """``profiled(body)`` runs ``body()`` under a ``jax.profiler`` trace and
    returns ``(body's result, host spans)``: the events of the trace's
    ``/host:CPU`` plane as :class:`HostSpan`, in start order."""
    import jax
    from jax.profiler import ProfileData

    def run(body):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            out = body()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        spans = [
            HostSpan(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines
            for ev in line.events
        ]
        return out, sorted(spans, key=lambda s: s.start_ns)

    return run
