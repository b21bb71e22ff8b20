"""The cells ``williamson5.ens51`` and ``swe2d.ens204.x4`` on the CPU at
small sizes: a sound run is correct, the control and every planted fault
are not, the plain sphere reference agrees with the program, ``bench/work``
counts the reference's own operations, and the four-chip cell runs sharded.

A state returned unchanged reads exactly 1.0 in ``williamson5.ens51`` (its
``hv`` is the initial zero), above the cell's limit of 0.95; R2F2-16 reads
up to 0.875 there at full size on the chip (PERF.md, §2).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import compare, harness
from bench.reference import swe_sphere
from bench.tests import _small, faults
from bench.tests.test_bench_reference import _program
from bench.tests.test_bench_work import _count_interior_ops

ROOT = Path(__file__).resolve().parents[2]
CELL = "williamson5.ens51"
#: 32 x 16 cells; dt = 120 s is Courant 0.24 on the polar row
SMALL = dict(fields={"nlon": 32, "nlat": 16, "dt": 120.0}, steps=200, snapshot_every=50)


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setitem(_small.SMALL, "williamson5_t42", SMALL)


def test_sound_run_is_correct(monkeypatch, small):
    line = _small.run(monkeypatch, CELL)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "member_steps_per_s"}


def test_control_is_not_correct(monkeypatch, small):
    line = _small.run(monkeypatch, CELL, control=True)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, small, fault):
    faults.plant(monkeypatch, fault)
    line = _small.run(monkeypatch, CELL)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("scales", [[1.0], [0.5, 1.5]])
def test_swe_sphere_reference_matches_program(scales):
    """Shared initial state and topography, 200 steps at 32 x 16. h and hu
    agree to about 5e-6; hv, small after 200 steps, to about 1.1e-4 (the two
    round the flux terms in different orders)."""
    config = harness.load_json("configs", "williamson5_t42")
    cut = SMALL["fields"]
    fields = dict(config["fields"], **cut)
    states = swe_sphere.initial_state(fields, np.array(scales))
    final, snaps = _program(config, cut, states, 200, 50)
    ref_final, ref_snaps = jax.vmap(lambda s: swe_sphere.run(fields, s, 200, 50))(states)
    gaps = compare.worst_member_gap(final, snaps, ref_final, ref_snaps, swe_sphere.offsets(fields))
    assert gaps.max() <= 1e-3


@pytest.mark.parametrize("nlon,nlat", [(8, 4), (32, 16), (128, 64)])
def test_sphere_step_flops_match_reference(nlon, nlat):
    work = harness.load_module("work", "williamson5_t42")
    cfg = dict(harness.load_json("configs", "williamson5_t42")["fields"], nlon=nlon, nlat=nlat)
    U = jax.numpy.ones((3, nlat, nlon), jax.numpy.float32)
    assert _count_interior_ops(lambda u: swe_sphere.step(u, cfg), U) == work.step_flops(cfg)


def test_sphere_horizon_work_and_least_bytes():
    config = harness.load_json("configs", "williamson5_t42")
    work = harness.load_module("work", "williamson5_t42")
    assert work.flops(config) == 10800 * 128 * (169 * 64 + 53)
    # state (3 fields) read once, 4 snapshots of h and the final state written
    assert work.hbm_bytes(config) == (3 + 4 + 3) * 128 * 64 * 4


SHARDED = """
import json, sys
import jax, pytest
from bench.tests import _small, faults
mp = pytest.MonkeyPatch()
if sys.argv[1] != "sound":
    faults.plant(mp, sys.argv[1])
line = _small.run(mp, "swe2d.ens204.x4", members=8)
print(json.dumps({"devices": line["device"]["count"], "attempted": line["attempted"],
                  "correct": line["correct"]}))
"""


@pytest.mark.parametrize("case", ["sound", "altered"])
def test_ens204_runs_sharded_on_four_devices(case):
    """The four-chip cell through the harness on four virtual CPU devices
    (members cut to 8, two a device)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run([sys.executable, "-c", SHARDED, case], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["devices"] == 4 and line["attempted"] % 8 == 0
    assert line["correct"] == (case == "sound")
