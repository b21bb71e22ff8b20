"""The comparison that decides ``correct``: relative L2 gaps to the plain
reference, the worst one counting.

A gap is ``||program - reference|| / ||reference - offset||`` over one
field of one member, where ``offset`` removes a resting background (the
basin depth of swe2d's ``h``) so that the gap judges the wave and not the
water under it. A program field with any non-finite value reads ``inf``.
"""

from __future__ import annotations

import numpy as np


def rel_l2(program, reference, offset: float = 0.0) -> float:
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    if not np.isfinite(p).all():
        return float("inf")
    return float(np.linalg.norm(p - r) / np.linalg.norm(r - offset))


def worst_member_gap(final, snaps, ref_final, ref_snaps, offsets) -> np.ndarray:
    """Per member, the worst gap over every field of the final state and
    every snapshot. ``final``/``ref_final`` lead with the member dim, then the
    field dim where the state has several fields; ``offsets`` gives each
    field's background (the snapshots take the first field's)."""
    final, ref_final = np.asarray(final), np.asarray(ref_final)
    snaps, ref_snaps = np.asarray(snaps), np.asarray(ref_snaps)
    out = np.zeros(final.shape[0])
    for m in range(final.shape[0]):
        fields = final[m][None] if final.ndim == 2 else final[m]
        ref_fields = ref_final[m][None] if ref_final.ndim == 2 else ref_final[m]
        gaps = [rel_l2(p, r, o) for p, r, o in zip(fields, ref_fields, offsets)]
        gaps += [rel_l2(p, r, offsets[0]) for p, r in zip(snaps[m], ref_snaps[m])]
        out[m] = max(gaps)
    return out
