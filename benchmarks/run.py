"""Benchmark harness: one function per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--only mul,heat,swe,pde,service,kernels,roofline]
                                            [--json-dir artifacts/bench] [--smoke]
                                            [--check] [--check-tol 10.0]

Most benches print ``name,us_per_call,derived`` CSV lines; the harness
captures them and emits one machine-readable ``BENCH_<suite>.json`` per
suite so the perf trajectory accumulates across commits (CI keeps these as
artifacts). Suites with non-CSV output (e.g. roofline's table) are kept as
raw text lines instead of parsed rows. JSON schema:

    {"suite": str, "unix_time": float, "backend": str, "git_sha": str|null,
     "rows": [{"name": str, "us_per_call": float, "derived": str}],
     "raw_lines": [str]}   # only when no CSV rows were found

``git_sha`` + ``backend`` pin every BENCH json to the commit and JAX
backend that produced it, so the accumulated artifact trajectory is
attributable without relying on CI-side bookkeeping.

``--check`` turns the harness into a regression gate: the committed
``BENCH_<suite>.json`` files already in ``--json-dir`` are loaded as the
baseline BEFORE the suites overwrite them, and every fresh row is compared
against the baseline row of the same name. Structural metrics regressing is
a hard failure (nonzero exit): ``bytes_per_step`` (the packed plane's
bandwidth claim) and ``launches`` (the megakernel's whole-horizon claim)
must not grow, and a nonzero health ``alerts`` count on a service row is
a hard failure too — the bench burst is healthy traffic, so an alert
firing during it means a numerics or serving regression. Wall time is
noisy, so ``us_per_call`` beyond ``--check-tol``
x the baseline only warns (and only when the fresh and baseline smoke tiers
match); a measured time BELOW the row's own analytic bandwidth bound
(``bytes_per_step / HBM_BW``) also warns — that is measurement error, not
speed. CI runs the smoke tier with ``--check`` after the bench step.
"""

import argparse
import contextlib
import inspect
import io
import json
import os
import subprocess
import time

SUITES = ("mul", "exploration", "heat", "swe", "pde", "service", "kernels", "roofline")


def _git_sha():
    """Commit that produced this BENCH json (None outside a git checkout)."""
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            text=True,
            stderr=subprocess.DEVNULL,
        ).strip()
    except Exception:
        return None


def _run_suite(name: str, smoke: bool = False) -> str:
    """Import lazily and run one suite, returning its captured stdout."""
    if name == "mul":
        from benchmarks import bench_mul_accuracy as mod
    elif name == "exploration":
        from benchmarks import bench_exploration as mod
    elif name == "heat":
        from benchmarks import bench_heat as mod
    elif name == "swe":
        from benchmarks import bench_swe as mod
    elif name == "pde":
        from benchmarks import bench_pde as mod
    elif name == "service":
        from benchmarks import bench_service as mod
    elif name == "kernels":
        from benchmarks import bench_kernels as mod
    elif name == "roofline":
        from benchmarks import roofline as mod
    else:
        raise ValueError(f"unknown suite {name!r}")

    # suites that implement a reduced-step smoke tier accept main(smoke=...);
    # the rest run their usual size regardless of --smoke
    kwargs = {}
    if smoke and "smoke" in inspect.signature(mod.main).parameters:
        kwargs["smoke"] = True
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            mod.main(**kwargs)
    except BaseException:
        # surface whatever the suite printed before dying, then the traceback
        print(buf.getvalue(), end="")
        raise
    return buf.getvalue()


def _parse_rows(text: str):
    """``name,us_per_call,derived`` CSV lines -> row dicts (others ignored)."""
    rows = []
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) < 2 or "/" not in parts[0]:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append(
            {
                "name": parts[0],
                "us_per_call": us,
                "derived": parts[2] if len(parts) > 2 else "",
            }
        )
    return rows


def _parse_derived(derived: str) -> dict:
    """``k=v;k=v`` derived strings -> dict (tokens without '=' ignored)."""
    out = {}
    for part in derived.split(";"):
        k, sep, v = part.partition("=")
        if sep:
            out[k] = v
    return out


#: derived keys whose growth vs the baseline is a hard --check failure:
#: bytes_per_step is the packed storage plane's bandwidth claim, launches
#: is the megakernel's one-pallas_call-per-horizon claim
CHECK_STRUCTURAL = ("bytes_per_step", "launches")


def check_records(fresh: dict, baselines: dict, tol: float = 10.0):
    """Compare fresh suite records against the committed baselines.

    Returns ``(failures, warnings)`` — string lists. Failures: a
    :data:`CHECK_STRUCTURAL` metric grew on a row present in both, or a
    service row reporting a nonzero health ``alerts`` count — the bench
    burst is healthy traffic, so any alert (overflow storm, k-thrash,
    SLO breach) firing during it is a real numerics/serving regression,
    baseline or not. Warnings:
    ``us_per_call`` beyond ``tol`` x baseline on matching smoke tiers, or a
    measured time below the row's own analytic bandwidth bound
    (``bytes_per_step`` at :data:`benchmarks.roofline.HBM_BW` — beating the
    roofline is measurement error, not speed).
    """
    from benchmarks.roofline import HBM_BW

    failures, warnings = [], []
    for suite, rec in fresh.items():
        base = baselines.get(suite)
        base_rows = (
            {r["name"]: r for r in base.get("rows", [])} if base is not None else {}
        )
        for row in rec.get("rows", []):
            d = _parse_derived(row.get("derived", ""))
            # health gate: alerts during the bench burst are a hard failure
            # with or without a baseline (the burst itself is healthy traffic)
            try:
                n_alerts = int(d.get("alerts", 0))
            except ValueError:
                n_alerts = 0
            if n_alerts > 0:
                failures.append(
                    f"{row['name']}: {n_alerts} health alert(s) fired in the "
                    "bench burst (expected a clean run)"
                )
            b = base_rows.get(row["name"])
            if b is not None:
                bd = _parse_derived(b.get("derived", ""))
                for key in CHECK_STRUCTURAL:
                    if key in d and key in bd and int(d[key]) > int(bd[key]):
                        failures.append(
                            f"{row['name']}: {key} regressed "
                            f"{bd[key]} -> {d[key]}"
                        )
                if (
                    base.get("smoke") == rec.get("smoke")
                    and b["us_per_call"] > 0
                    and row["us_per_call"] > tol * b["us_per_call"]
                ):
                    warnings.append(
                        f"{row['name']}: us_per_call {b['us_per_call']:.2f} -> "
                        f"{row['us_per_call']:.2f} "
                        f"({row['us_per_call'] / b['us_per_call']:.1f}x baseline, "
                        f"tol {tol:.1f}x)"
                    )
            # bound sanity only applies to MEASURED rows — the roofline
            # suite's rows ARE the analytic bound and would flag themselves
            if "bytes_per_step" in d and not row["name"].startswith("roofline/"):
                bound_us = float(d["bytes_per_step"]) / HBM_BW * 1e6
                if 0 < row["us_per_call"] < bound_us:
                    warnings.append(
                        f"{row['name']}: measured {row['us_per_call']:.4f}us "
                        f"beats the analytic bandwidth bound {bound_us:.4f}us "
                        "— measurement error?"
                    )
    return failures, warnings


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument(
        "--json-dir",
        default=".",
        help="directory for BENCH_<suite>.json files (created if missing)",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="reduced-step tier for per-push CI (suites that support it)",
    )
    ap.add_argument(
        "--check",
        action="store_true",
        help="gate fresh rows against the BENCH jsons committed in "
        "--json-dir (loaded before the suites overwrite them); structural "
        "regressions (bytes_per_step, launches) exit nonzero",
    )
    ap.add_argument(
        "--check-tol",
        type=float,
        default=10.0,
        help="us_per_call warn threshold as a multiple of the baseline",
    )
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    os.makedirs(args.json_dir, exist_ok=True)

    # --check baselines: snapshot the committed jsons before overwriting
    baselines = {}
    if args.check:
        for suite in SUITES:
            path = os.path.join(args.json_dir, f"BENCH_{suite}.json")
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        baselines[suite] = json.load(f)
                except (OSError, ValueError):
                    pass  # unreadable baseline: nothing to gate against

    import jax

    git_sha = _git_sha()
    fresh = {}
    for suite in SUITES:
        if only is not None and suite not in only:
            continue
        text = _run_suite(suite, smoke=args.smoke)
        print(text, end="")
        print()
        record = {
            "suite": suite,
            "unix_time": time.time(),
            "backend": jax.default_backend(),
            "git_sha": git_sha,
            "smoke": args.smoke,
            "rows": _parse_rows(text),
        }
        if not record["rows"]:  # non-CSV suite: keep the output verbatim
            record["raw_lines"] = [l for l in text.splitlines() if l.strip()]
        fresh[suite] = record
        path = os.path.join(args.json_dir, f"BENCH_{suite}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=2)
        n = len(record["rows"]) or len(record.get("raw_lines", []))
        kind = "rows" if record["rows"] else "raw lines"
        print(f"[bench] wrote {path} ({n} {kind})")

    if args.check:
        failures, warnings = check_records(fresh, baselines, tol=args.check_tol)
        for w in warnings:
            print(f"[bench --check] WARN {w}")
        for f_ in failures:
            print(f"[bench --check] FAIL {f_}")
        checked = [s for s in fresh if s in baselines]
        print(
            f"[bench --check] {len(checked)} suite(s) gated "
            f"({', '.join(checked) or 'none with baselines'}): "
            f"{len(failures)} failure(s), {len(warnings)} warning(s)"
        )
        if failures:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
