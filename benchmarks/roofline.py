"""Roofline analysis: PDE storage-traffic rows + LM dry-run table.

The PDE section is analytic and always runs (no artifacts needed): per
registered stepper x carried-storage format, the bytes one step moves
across the HBM boundary (2x the carried-state footprint — one read, one
write) and the memory-roofline time that traffic costs at HBM bandwidth.
The ``packed`` rows carry R2F2 payloads (``repro.pack``) instead of f32;
their bytes-per-step ratio against the f32 rows is the bandwidth headline
the packed execution plane banks. Emitted as ``name,us,derived`` CSV so
``benchmarks.run`` captures them into ``BENCH_roofline.json``.

The LM table below it analyzes compiled dry-run artifacts (deliverable g).

Per (arch x shape x mesh) cell, from artifacts/dryrun/<cell>.json:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs          [s]
    memory term     = HLO_bytes_per_device / HBM_bw              [s]
    collective term = collective_bytes_per_device / link_bw      [s]

(cost_analysis of the SPMD-partitioned executable is already per-device, so
the prompt's "/ chips" is folded in.) Hardware: the peaks of
:data:`TARGET_KIND` from :data:`PEAKS` (we charge the busiest single ICI
link, a conservative serialization bound).

Also reported: MODEL_FLOPS (6ND train / 2ND forward, N_active for MoE), the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs (catches remat & masked-block
waste), the dominant term, and roofline fraction = dominant / sum-of-terms
upper-bounded step time.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import SHAPES, get_config

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": dict(
        flops_bf16=197e12,  # FLOP/s
        hbm_bytes_per_s=819e9,
        # 1,600 Gbit/s chip-to-chip interconnect over 4 ICI links
        ici_link_bytes_per_s=1600e9 / 8 / 4,
        source="Google Cloud documentation, 'TPU v5e' (system architecture)",
    ),
}
#: the chip this analytic model describes (TPU v5e)
TARGET_KIND = "TPU v5 lite"


def peaks(kind: str) -> Dict:
    """The peak table row for a device kind; an unknown kind is an error,
    never a default."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[kind]


PEAK_FLOPS = peaks(TARGET_KIND)["flops_bf16"]
HBM_BW = peaks(TARGET_KIND)["hbm_bytes_per_s"]
LINK_BW = peaks(TARGET_KIND)["ici_link_bytes_per_s"]
#: ASSUMED, not measured: fixed dispatch cost charged per pallas_call launch
#: (host->device setup, grid program bring-up) — the term the megakernel
#: amortizes: a chunked horizon pays it steps/every times, the megakernel
#: exactly once. No chip trace has measured it yet.
LAUNCH_OVERHEAD_US = 4.0

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def model_flops_per_device(arch: str, shape_name: str, chips: int) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n * shape.global_batch
    return total / chips


def analyze_cell(r: Dict) -> Optional[Dict]:
    if r.get("status") != "ok":
        return None
    chips = r["chips"]
    # trip-count-corrected rollup (launch/hlo_cost.py); raw cost_analysis
    # counts loop bodies once and is kept in the artifact for reference
    cor = r.get("corrected")
    if cor:
        flops = cor["flops_per_device"]
        bytes_acc = cor["bytes_per_device"]
        coll = sum(cor["collective_bytes"].values())
    else:
        flops = r["flops_per_device"]
        bytes_acc = r["bytes_accessed_per_device"]
        coll = sum(r["collective_bytes"].values())

    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_coll = coll / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    mflops = model_flops_per_device(r["arch"], r["shape"], chips)
    useful = mflops / flops if flops > 0 else 0.0
    # roofline fraction: useful compute time over the overlap-free bound
    t_bound = max(terms.values())
    frac = (mflops / PEAK_FLOPS) / t_bound if t_bound > 0 else 0.0

    hbm_gib = (r["memory"]["argument_bytes"] + r["memory"]["temp_bytes"]) / 2**30
    return dict(
        cell=r["cell"],
        arch=r["arch"],
        shape=r["shape"],
        mesh=r["mesh"],
        t_compute_s=t_compute,
        t_memory_s=t_memory,
        t_collective_s=t_coll,
        dominant=dominant,
        useful_ratio=useful,
        roofline_frac=frac,
        hbm_gib_per_dev=hbm_gib,
        fits_16g=hbm_gib < 16.0,
    )


def load_all(mesh: str = "16x16") -> List[Dict]:
    rows = []
    for f in sorted(glob.glob(os.path.join(ART, f"*__{mesh}.json"))):
        r = json.load(open(f))
        if r["status"] == "skip":
            rows.append(dict(cell=r["cell"], skip=r["reason"]))
            continue
        a = analyze_cell(r)
        if a:
            rows.append(a)
        else:
            rows.append(dict(cell=r["cell"], skip="ERROR: " + r.get("error", "?")[:60]))
    return rows


def pde_storage_rows():
    """Analytic bytes-moved-per-step rows, per stepper x storage format.

    Pure metadata arithmetic — packs each stepper's initial state once to
    measure the carried footprint; nothing is stepped or jitted.
    """
    import jax

    from repro.pack import pack_state, state_nbytes
    from repro.pde import get_stepper, known_steppers
    from repro.precision import PRESETS

    fmt = PRESETS["r2f2_16"].fmt
    rows = []
    for name in known_steppers():
        stepper = get_stepper(name)
        cfg = stepper.default_config()
        state = jax.tree_util.tree_map(jax.numpy.asarray, stepper.init_state(cfg))
        f32_bytes = 2 * state_nbytes(state)
        packed_bytes = 2 * state_nbytes(pack_state(state, fmt))
        for storage, nbytes in (("f32", f32_bytes), ("packed", packed_bytes)):
            t_mem_us = nbytes / HBM_BW * 1e6
            rows.append(
                (
                    f"roofline/pde/{name}/{storage}",
                    t_mem_us,
                    f"bytes_per_step={nbytes}"
                    f";ratio_vs_f32={nbytes / f32_bytes:.3f}"
                    f";hbm_bw_gbps={HBM_BW / 1e9:.0f}",
                )
            )
    return rows


def pde_step_bound_us(nbytes_per_step: float, steps: int, launches: int) -> float:
    """Analytic per-step lower bound for one horizon: boundary HBM traffic
    at bandwidth + the fixed launch overhead amortized over the horizon's
    steps. The bench's measured us_per_step can approach but not beat this
    (``benchmarks.run --check`` flags rows that do as measurement noise)."""
    return nbytes_per_step / HBM_BW * 1e6 + LAUNCH_OVERHEAD_US * launches / steps


def pde_launch_rows(steps: int = 240):
    """Chunked-vs-megakernel launch-overhead model, per stepper x storage.

    For each registered stepper's default config and snapshot cadence: the
    chunked fused plane issues one pallas_call per snapshot interval
    (``steps/every`` launches per horizon, remainder included) while the
    megakernel issues exactly 1. Each row reports the per-step analytic
    bound (:func:`pde_step_bound_us`), its two terms, and which one
    dominates — ``launch``-bound horizons are the megakernel's win case,
    ``bandwidth``-bound ones are the packed plane's. Pure metadata
    arithmetic, nothing is stepped or jitted.
    """
    import jax

    from repro.pack import pack_state, state_nbytes
    from repro.pde import get_stepper, known_steppers
    from repro.precision import PRESETS

    fmt = PRESETS["r2f2_16"].fmt
    rows = []
    for name in known_steppers():
        stepper = get_stepper(name)
        cfg = stepper.default_config()
        state = jax.tree_util.tree_map(jax.numpy.asarray, stepper.init_state(cfg))
        every = max(1, steps // stepper.snapshots_default)
        n_chunks = steps // every + (1 if steps % every else 0)
        for storage, nbytes in (
            ("f32", 2 * state_nbytes(state)),
            ("packed", 2 * state_nbytes(pack_state(state, fmt))),
        ):
            for plane, launches in (("chunked", n_chunks), ("megakernel", 1)):
                t_mem_us = nbytes / HBM_BW * 1e6
                t_launch_us = LAUNCH_OVERHEAD_US * launches / steps
                bound = pde_step_bound_us(nbytes, steps, launches)
                rows.append(
                    (
                        f"roofline/pde_launch/{name}/{plane}/{storage}",
                        bound,
                        f"launches={launches};steps={steps}"
                        f";bytes_per_step={nbytes}"
                        f";t_mem_us={t_mem_us:.4f};t_launch_us={t_launch_us:.4f}"
                        f";bound={'launch' if t_launch_us > t_mem_us else 'bandwidth'}"
                        f";launch_overhead_us_assumed={LAUNCH_OVERHEAD_US}",
                    )
                )
    return rows


def main():
    print("# roofline — PDE carried-state HBM traffic per step (analytic)")
    print("# us column = memory-roofline time of one step's state traffic")
    for name, us, derived in pde_storage_rows():
        print(f"{name},{us:.4f},{derived}")
    print()
    print("# roofline — chunked-vs-megakernel launch model (analytic)")
    print("# us column = per-step bound: HBM traffic + amortized launch overhead")
    for name, us, derived in pde_launch_rows():
        print(f"{name},{us:.4f},{derived}")
    print()
    print("# roofline — single-pod 16x16 (256 chips); terms in ms per step")
    print(
        f"{'cell':58s} {'comp':>7s} {'mem':>7s} {'coll':>7s} "
        f"{'dominant':>10s} {'useful':>7s} {'frac':>6s} {'HBM':>7s}"
    )
    for row in load_all("16x16"):
        if "skip" in row:
            print(f"{row['cell']:58s} SKIP: {row['skip']}")
            continue
        print(
            f"{row['cell']:58s} "
            f"{row['t_compute_s']*1e3:7.2f} {row['t_memory_s']*1e3:7.2f} "
            f"{row['t_collective_s']*1e3:7.2f} {row['dominant']:>10s} "
            f"{row['useful_ratio']:7.3f} {row['roofline_frac']:6.3f} "
            f"{row['hbm_gib_per_dev']:6.2f}G"
        )
    print("\n# multi-pod 2x16x16 (512 chips)")
    for row in load_all("2x16x16"):
        if "skip" in row:
            continue
        print(
            f"{row['cell']:58s} "
            f"{row['t_compute_s']*1e3:7.2f} {row['t_memory_s']*1e3:7.2f} "
            f"{row['t_collective_s']*1e3:7.2f} {row['dominant']:>10s} "
            f"{row['useful_ratio']:7.3f} {row['roofline_frac']:6.3f} "
            f"{row['hbm_gib_per_dev']:6.2f}G"
        )


if __name__ == "__main__":
    main()
