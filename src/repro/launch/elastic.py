"""Elastic rescale: resume a checkpoint on a DIFFERENT device count/mesh.

Checkpoints are mesh-agnostic (host numpy + manifest), so elastic scaling is
"restore with the new mesh's shardings". The data pipeline being a pure
function of step means the token stream is unaffected by the re-shard; only
the per-host batch slices change.

    PYTHONPATH=src python -m repro.launch.elastic --devices 8 --arch ... \
        --ckpt-dir /tmp/ckpt --steps 10

spawns itself with ``xla_force_host_platform_device_count=<devices>`` and
continues training on the new mesh (examples/elastic_restart.py demos the
full failure -> shrink -> resume cycle). The child runs on virtual CPU
devices (``JAX_PLATFORMS=cpu``): this is a CPU tool, and a child that
reached for an accelerator its parent may hold would fail or hang.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys


def respawn_with_devices(n_devices: int, argv):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + env.get("XLA_FLAGS", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_ELASTIC_CHILD"] = "1"
    cmd = [sys.executable, "-m", "repro.launch.elastic"] + argv
    return subprocess.run(cmd, env=env).returncode


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--seed", type=int, default=17)
    args, rest = ap.parse_known_args()

    if args.devices and not os.environ.get("REPRO_ELASTIC_CHILD"):
        argv = [a for a in sys.argv[1:] if not a.startswith("--devices")]
        argv = [a for i, a in enumerate(argv) if not (a == str(args.devices) and sys.argv[sys.argv.index(a) - 1] == "--devices")]
        raise SystemExit(respawn_with_devices(args.devices, argv))

    # child (or direct) path: restore on whatever mesh exists now
    import jax

    from repro.launch.train import main as train_main

    print(f"[elastic] resuming on {len(jax.devices())} devices")
    train_main(
        [
            "--arch", args.arch,
            *(["--reduced"] if args.reduced else []),
            "--steps", str(args.steps),
            "--batch", str(args.batch),
            "--seq", str(args.seq),
            "--ckpt-dir", args.ckpt_dir,
            "--resume",
            "--seed", str(args.seed),
        ]
    )


if __name__ == "__main__":
    main()
