"""The ensemble driver's sharded path (members split over a one-axis mesh by
``run_ensemble(sharded=True)``) on four virtual CPU devices, in a process of
its own since the device count is fixed when JAX starts: a sound run is
correct, a planted fault is not."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = """
import json, sys
import jax, pytest
from bench import harness
from bench.tests import _small, faults
mp = pytest.MonkeyPatch()
if sys.argv[1] != "sound":
    faults.plant(mp, sys.argv[1])
load = _small.small_loader(members=8)
config, mix = load("configs", "swe2d_128"), load("traffic", "ens51")
driver = harness.load_module("traffic", "ensemble")
run = driver.Run(config=config, mix=mix, seed=2**31 + 5, seconds=1.0, devices=jax.devices())
run.setup()
run.window()
run.free()
checks = run.check()
print(json.dumps({"devices": len(jax.devices()), "sharded": run.mesh is not None,
                  "correct": all(v <= lim for _, v, lim in checks)}))
"""


@pytest.mark.parametrize("case", ["sound", "altered", "half_batch"])
def test_sharded_ensemble_on_four_devices(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run([sys.executable, "-c", SCRIPT, case], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["devices"] == 4 and line["sharded"]
    assert line["correct"] == (case == "sound")
