"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

Copied from ``benchmarks/roofline.py:PEAKS`` so that the yardstick lives with
the benchmark. A device kind that is not here is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": dict(
        flops_bf16=197e12,  # FLOP/s
        hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e' (system architecture)",
    ),
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[kind]
