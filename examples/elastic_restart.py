"""Failure + elastic-rescale demo: train on N devices, 'lose' the job, resume
on a DIFFERENT device count from the latest atomic checkpoint.

    PYTHONPATH=src python examples/elastic_restart.py

Each phase is a child process on virtual CPU devices (``JAX_PLATFORMS=cpu``):
this demo never touches an accelerator.
"""

import os
import shutil
import subprocess
import sys
import tempfile

CKPT = os.path.join(tempfile.gettempdir(), "repro_elastic_demo")


def run(n_devices, steps, extra=()):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "stablelm-12b", "--reduced",
        "--steps", str(steps), "--batch", "8", "--seq", "64",
        "--ckpt-dir", CKPT, "--ckpt-every", "10", *extra,
    ]
    print(f"\n$ devices={n_devices} " + " ".join(cmd[2:]))
    return subprocess.run(cmd, env=env).returncode


def main():
    shutil.rmtree(CKPT, ignore_errors=True)
    print("=== phase 1: train on 4 devices, inject failure at step 25 ===")
    run(4, 40, ["--inject-failure-at", "25"])
    print("\n=== phase 2: cluster shrank — resume on 2 devices ===")
    run(2, 40, ["--resume"])
    print("\nelastic restart complete: same loss trajectory, half the devices.")


if __name__ == "__main__":
    main()
