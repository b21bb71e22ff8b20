import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("REPRO_NATIVE_BF16", "1")  # accurate HLO byte accounting

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, on BOTH the single-pod
(16, 16) and multi-pod (2, 16, 16) production meshes:

    with mesh:
        lowered  = jax.jit(step_fn, in_shardings=...).lower(*input_specs)
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

Results (memory/FLOP/collective-bytes per cell) land in
``artifacts/dryrun/<cell>.json``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes [--skip-existing]
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, applicability, cell_window, get_config
from repro.precision import PRESETS
from repro.launch.hlo_cost import parse_hlo_costs
from repro.dist.sharding import axis_rules
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import decode_specs, prefill_specs, train_specs
from repro.models import prefill
from repro.train.step import TrainConfig, make_serve_step, make_train_step

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(tok_dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[tok_dtype]


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op in the partitioned HLO,
    split by op kind. (Result bytes ~ payload; all-gather results count the
    gathered size, reduce-scatter the scattered size — a consistent,
    conservative proxy; see EXPERIMENTS.md §Roofline notes.)"""
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w\.\-]+ = (.+)", ls)
        if m is None:
            continue
        rest = m.group(1)
        for c in COLLECTIVES:
            # match the op name, not substrings of other ops
            if re.search(rf"\b{c}(-start|-done)?\(", rest):
                if c == "all-reduce" and "all-reduce-done" in rest:
                    continue  # payload counted at -start
                shapes = _SHAPE_RE.findall(rest.split("(")[0])
                total = sum(_shape_bytes(t, d) for t, d in shapes)
                out[c] += total
                counts[c] += 1
                break
    return out, counts


# §Perf hillclimb: per-cell tuned training configs for the three selected
# cells (EXPERIMENTS.md §Perf documents each hypothesis->measurement cycle).
# All other cells run the plain baseline TrainConfig.
from repro.train.optimizer import OptConfig  # noqa: E402

TRAIN_OVERRIDES = {
    # memory-bound at 550 GiB/dev temp (126 f32 scan boundaries + B_loc=16
    # activations); 16 microbatches + bf16 boundaries + factored optimizer
    "llama3-405b": dict(
        microbatches=8, carry_dtype="bf16", opt=OptConfig(kind="adafactor")
    ),
    # collective-bound (94 groups x 128-expert FSDP all-gathers) + memory
    "qwen3-moe-235b-a22b": dict(microbatches=4, carry_dtype="bf16"),
    # memory-bound hybrid (mamba state expansion + MoE); chunk-local
    # selective scan (ssm.py) is the structural half of this iteration
    "jamba-v0.1-52b": dict(microbatches=4, carry_dtype="bf16"),
}


def build_cell(arch: str, shape_name: str, mesh, prec_name: str = "deploy"):
    """Returns (fn, in_shardings, args_sds) for one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    prec = PRESETS[prec_name]
    window = cell_window(cfg, shape)

    if shape.kind == "train":
        over = {} if os.environ.get("REPRO_NO_OVERRIDES") else TRAIN_OVERRIDES.get(arch, {})
        tcfg = TrainConfig(window=window, **over)
        (state_sds, b_sds), (state_sh, b_sh), _ = train_specs(cfg, shape, tcfg, mesh)
        fn = make_train_step(cfg, prec, tcfg, param_shardings=state_sh["params"])
        return fn, (state_sh, b_sh), (state_sds, b_sds)

    if shape.kind == "prefill":
        (p_sds, b_sds), (p_sh, b_sh) = prefill_specs(cfg, shape, mesh)

        def fn(params, batch):
            return prefill(
                params,
                cfg,
                prec,
                tokens=batch.get("tokens"),
                embeds=batch.get("embeds"),
                max_len=shape.seq_len,
                window=window,
            )

        return fn, (p_sh, b_sh), (p_sds, b_sds)

    # decode
    (p_sds, c_sds, t_sds, pos_sds), (p_sh, c_sh, t_sh, pos_sh) = decode_specs(
        cfg, shape, mesh
    )
    fn = make_serve_step(cfg, prec, window=window)
    return fn, (p_sh, c_sh, t_sh, pos_sh), (p_sds, c_sds, t_sds, pos_sds)


def run_cell(arch: str, shape_name: str, multi_pod: bool, save: bool = True, verbose=True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = applicability(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    if skip:
        result = {"cell": cell_id, "status": "skip", "reason": skip}
        if save:
            _save(cell_id, result)
        if verbose:
            print(f"[skip] {cell_id}: {skip}")
        return result

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        with mesh, axis_rules(mesh):
            fn, in_sh, args_sds = build_cell(arch, shape_name, mesh)
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args_sds)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            coll, coll_counts = collective_bytes(hlo)
            # trip-count-aware rollup (XLA cost_analysis counts loop bodies
            # once; see launch/hlo_cost.py) — the roofline reads these.
            corrected = parse_hlo_costs(hlo)

        n_chips = mesh.devices.size
        result = {
            "cell": cell_id,
            "status": "ok",
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_name,
            "chips": int(n_chips),
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "flops_per_device": float(cost.get("flops", -1.0)),
            "bytes_accessed_per_device": float(cost.get("bytes accessed", -1.0)),
            "memory": {
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", -1)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", -1)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
                "alias_bytes": int(getattr(mem, "alias_size_in_bytes", -1)),
            },
            "collective_bytes": coll,
            "collective_counts": coll_counts,
            "corrected": {
                "flops_per_device": corrected["flops"],
                "bytes_per_device": corrected["bytes"],
                "collective_bytes": corrected["collective_bytes"],
                "collective_counts": corrected["collective_counts"],
            },
            "params_B": round(cfg.param_count() / 1e9, 3),
            "active_params_B": round(cfg.active_param_count() / 1e9, 3),
        }
        if verbose:
            m = result["memory"]
            print(
                f"[ok]   {cell_id}: compile {t_compile:.0f}s, "
                f"{corrected['flops']/1e9:.1f} GFLOP/dev (raw {result['flops_per_device']/1e9:.1f}), "
                f"args {m['argument_bytes']/2**30:.2f} GiB/dev, "
                f"temp {m['temp_bytes']/2**30:.2f} GiB/dev, "
                f"coll {sum(corrected['collective_bytes'].values())/2**20:.1f} MiB/dev"
            )
    except Exception as e:  # a failure here is a bug in the system
        result = {
            "cell": cell_id,
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "trace": traceback.format_exc()[-4000:],
        }
        if verbose:
            print(f"[FAIL] {cell_id}: {type(e).__name__}: {str(e)[:300]}")
    if save:
        _save(cell_id, result)
    return result


def _save(cell_id, result):
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, cell_id + ".json"), "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true", help="use the 2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    ok = fail = skip = 0
    for a, s, mp in cells:
        cid = f"{a}__{s}__{'2x16x16' if mp else '16x16'}"
        path = os.path.join(ARTIFACTS, cid + ".json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                r = json.load(f)
            print(f"[cached] {cid}: {r['status']}")
        else:
            r = run_cell(a, s, mp)
        ok += r["status"] == "ok"
        fail += r["status"] == "error"
        skip += r["status"] == "skip"
    print(f"\ndry-run summary: {ok} ok, {skip} skipped (by rule), {fail} FAILED")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
