"""Fault-tolerant training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mistral-nemo-12b \
        --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt \
        --ckpt-every 10 [--resume] [--inject-failure-at 25]

Fault-tolerance model (DESIGN.md §6):
  * checkpoints are atomic and mesh-agnostic (repro.ckpt);
  * the data pipeline is a pure function of the step index, so
    restart-from-latest replays *exactly* the batches the lost steps saw;
  * --inject-failure-at simulates a node failure mid-run; rerunning with
    --resume must produce bit-identical training to an uninterrupted run
    (tests/test_fault_tolerance.py asserts this);
  * straggler mitigation: per-step wall-clock watchdog logs steps slower
    than --straggler-grace x the running median (on real pods this is where
    you fire the preemption/respawn hook).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp

from repro.ckpt import latest_step, restore, save
from repro.configs import get_config, reduced
from repro.precision import PRESETS
from repro.data import batch_for_step
from repro.dist.sharding import axis_rules
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train import OptConfig, TrainConfig, init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--precision", default="deploy", choices=list(PRESETS))
    ap.add_argument("--opt", default="adamw", choices=["adamw", "adafactor"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-comm", default=None, choices=[None, "bf16", "rr16"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--straggler-grace", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    prec = PRESETS[args.precision]
    tcfg = TrainConfig(
        opt=OptConfig(kind=args.opt, lr=args.lr, total_steps=args.steps),
        microbatches=args.microbatches,
        grad_comm=args.grad_comm,
    )

    mesh = make_host_mesh()
    with mesh, axis_rules(mesh):
        state = init_train_state(jax.random.PRNGKey(args.seed), cfg, tcfg)
        start = 0
        if args.resume and args.ckpt_dir:
            last = latest_step(args.ckpt_dir)
            if last is not None:
                state = restore(state, args.ckpt_dir, last)
                start = last
                print(f"[resume] restored step {last} from {args.ckpt_dir}")

        step_fn = jax.jit(make_train_step(cfg, prec, tcfg))
        times = []
        for step in range(start, args.steps):
            if args.inject_failure_at is not None and step == args.inject_failure_at:
                print(f"[failure-injection] simulated node failure at step {step}")
                raise SystemExit(42)

            t0 = time.time()
            batch = batch_for_step(cfg, step, args.batch, args.seq, seed=args.seed)
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])  # sync point
            dt = time.time() - t0
            times.append(dt)

            if len(times) > 5:
                med = statistics.median(times[-50:])
                if dt > args.straggler_grace * med:
                    print(
                        f"[straggler] step {step} took {dt:.2f}s "
                        f"({dt/med:.1f}x median {med:.2f}s)"
                    )
            if step % args.log_every == 0 or step == args.steps - 1:
                print(
                    f"step {step:5d} loss {loss:.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} "
                    f"lr {float(metrics['lr']):.2e} ({dt*1e3:.0f} ms)"
                )
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                path = save(state, args.ckpt_dir, step + 1)
                print(f"[ckpt] step {step+1} -> {path}")

        if args.ckpt_dir:
            save(state, args.ckpt_dir, args.steps)
        print(f"done: final loss {loss:.4f}")
        return loss


if __name__ == "__main__":
    main()
