"""Shared test config: the hypothesis profile and the ``profiled`` fixture.

The ``repro_ci`` profile sets ``deadline=None`` (a property's first example
compiles) and a default budget of ``REPRO_HYPOTHESIS_EXAMPLES`` examples
(50 when unset). A test's own ``@settings(max_examples=...)`` wins over the
profile.
"""

import glob
import os
from typing import Any, Dict, NamedTuple

import hypothesis
import pytest

hypothesis.settings.register_profile(
    "repro_ci",
    max_examples=int(os.environ.get("REPRO_HYPOTHESIS_EXAMPLES", "50")),
    deadline=None,
)
hypothesis.settings.load_profile("repro_ci")


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
