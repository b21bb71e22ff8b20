"""``correct`` of the ensemble cells, on the CPU at small sizes: a sound run
passes, the control and every planted fault fail."""

import pytest

from bench.tests import _small, faults

CELLS = ["swe2d.ens51", "swe2d.ens51.f32"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(monkeypatch, cell):
    line = _small.run(monkeypatch, cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "member_steps_per_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(monkeypatch, cell):
    line = _small.run(monkeypatch, cell, control=True)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(monkeypatch, cell, fault):
    faults.plant(monkeypatch, fault)
    line = _small.run(monkeypatch, cell)
    assert not line["correct"], line["checks"]
