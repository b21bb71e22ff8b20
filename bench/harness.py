"""Run one benchmark cell once and print its result line.

    python3 -m bench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` and the mix's driver in
``bench/traffic/<kind>.py``, the configuration's work in
``bench/work/<config>.py``, each per-layer metric's reader in
``bench/metrics/<metric>.py``.

A run: find the chips (none, or fewer than the cell asks for, is an error
and prints no result); set up the traffic (build, compile, warm up: that is
``setup_s``, counted from the start of the process); measure for
``--seconds`` (with ``--trace 1`` under the profiler, whose trace is reduced
to the per-layer metrics); read the device's peak memory; free the program's
state; compare what the window produced with the plain reference. The
numbers compared are printed, each beside its limit, as the last lines of
standard error and under ``checks`` in the result, the last line of
standard output.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(kind: str, name: str, bench: Path = BENCH) -> dict:
    with open(bench / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: Path = BENCH) -> types.ModuleType:
    """``bench/<kind>/<name>.py`` as a module; metric names hold dots, so
    the file is loaded by path."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(spec: dict, section: str, cell: str) -> list:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in spec[section] if cell in m.get("workloads", [cell])]


def result_line(*, correct, attempted, failed, metrics, device, checks, breakdown=None) -> dict:
    """The contract's last line; ``checks`` comes last."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {name: {"value": _number(value), "limit": limit}
                      for name, value, limit in checks}
    return line


def _number(value):
    """A check's value as JSON can hold it: a non-finite one as a string."""
    value = float(value)
    return value if math.isfinite(value) else str(value)


def find_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked for, {len(devices)} found")
    return devices[:chips]


def enable_cache() -> None:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``, keeping every program however small."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts XLA compilations (JAX's own monitoring events) while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if self.on and event == self.EVENT:
            self.n += 1


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(args, spec: dict, devices, t_start: float):
    """Everything after the look for chips. Returns the result line."""
    import jax

    import repro.obs as obs
    import repro.obs.health as health

    obs.disable()
    health.disable()
    cell = find_cell(spec, args.workload)
    config = load_json("configs", cell["config"])
    mix = load_json("traffic", cell["traffic"])
    driver = load_module("traffic", mix["kind"])
    run = driver.Run(config=config, mix=mix, seed=args.seed, seconds=args.seconds,
                     devices=devices, control=args.control)
    run.setup()
    setup_s = time.perf_counter() - t_start
    compiles = CompileCounter()
    trace_dir = TRACE_DIR / cell["name"]
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans are the bench.* annotations
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    compiles.on = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            run.window()
    finally:
        compiles.on = False
        if args.trace:
            jax.profiler.stop_trace()
    scalars = {k: v for k, v in run.counts.items() if isinstance(v, (int, float))}
    print(f"bench: window {scalars}; XLA compiles in the window: {compiles.n}; "
          f"set-up {setup_s:.3f} s", file=sys.stderr, flush=True)
    memory = peak_memory(devices)
    end_to_end = run.end_to_end()
    run.free()
    checks = run.check()
    correct = all(value <= limit for _, value, limit in checks)

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    metrics, breakdown = {}, None
    if args.trace:
        from bench import reduce
        from bench.peaks import peaks

        trace = reduce.reduce(reduce.load(str(trace_dir)))
        ctx = types.SimpleNamespace(
            trace=trace, counts=run.counts, config=config,
            work=load_module("work", cell["config"]), peaks=peaks(d0.device_kind),
        )
        for m in metrics_of(spec, "per_layer", cell["name"]):
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        breakdown = {"device_ops": [list(x) for x in trace.top_ops()],
                     "idle_gaps": [list(x) for x in trace.top_idle()]}
    else:
        values = dict(end_to_end, setup_s=setup_s)
        for m in metrics_of(spec, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": units[m["name"]]}
    return result_line(correct=correct, attempted=run.attempted, failed=run.failed,
                       metrics=metrics, device=device, checks=checks, breakdown=breakdown)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the mix's control precision in place of its own "
                         "(a reading for the limit; its result is expected to be incorrect)")
    return ap.parse_args(argv)


def main(argv=None, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    spec = load_spec()
    cell = find_cell(spec, args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs at a fixed /tmp path
    try:
        devices = find_devices(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_cache()
    line = run_cell(args, spec, devices, t_start)
    for name, check in line["checks"].items():
        print(f"check {name} = {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
