"""``correct`` of the served cell, on the CPU at small sizes: a sound run
passes, the control and every planted fault fail."""

import pytest

from bench.tests import _small, faults

CELL = "heat1d.served"
# the control drifts from the reference as the field decays: on 32 points
# 250 steps decay the sine as far (to about 2e-4) as 4000 steps do on 128
LONG = {"heat1d_128": dict(steps=250, snapshot_every=50)}


def test_sound_run_is_correct(monkeypatch):
    line = _small.run(monkeypatch, CELL, **LONG)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 20
    assert set(line["metrics"]) == {"setup_s", "request_ms_p50"}


def test_control_is_not_correct(monkeypatch):
    line = _small.run(monkeypatch, CELL, control=True, **LONG)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    line = _small.run(monkeypatch, CELL)
    assert not line["correct"], line["checks"]
