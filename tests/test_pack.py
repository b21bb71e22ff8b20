"""Bit-level + integration suites for packed R2F2 storage (repro.pack).

Property tests pin the storage law: ``unpack(pack(x))`` IS ``quantize_em``
at the block's chosen split (pack/unpack bijective on quantized values),
across every reachable k, block granularity, and the padding crop. The
integration half asserts the design rule the solver builds on — a run
carrying ``storage="packed"`` state is bit-identical to the f32-carried
``storage="quantized"`` run on every stepper and plane — plus the service
legs: bucket separation by storage format and evict->resume parity through
``repro.ckpt`` with PackedArray state.
"""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import FlexFormat, quantize_em
from repro.core.policy import PrecisionConfig
from repro.pack import (
    PackedArray,
    block_storage_k,
    is_packed,
    pack_array,
    pack_state,
    payload_dtype,
    state_nbytes,
    storage_quantize,
    unpack_array,
    unpack_state,
)
from repro.pde import Simulation, get_stepper, known_steppers

FMT = FlexFormat(3, 9, 3)

STEPPER_SMALL_CFG = {
    "heat1d": {"nx": 64},
    "heat2d": {"nx": 16, "ny": 16},
    "advection1d": {"nx": 64},
    "burgers1d": {"nx": 64},
    "swe2d": {"nx": 16, "ny": 16},
    "swe_sphere": {"nlon": 16, "nlat": 8},
}


def _small_cfg(name):
    return dataclasses.replace(
        get_stepper(name).default_config(), **STEPPER_SMALL_CFG[name]
    )


# ---------------------------------------------------------------- properties


# Shapes come from fixed lists, not free integers: pack/unpack and the
# quantize_em oracle compile once per array and block shape, so a fresh shape
# per example would spend each example compiling. ``e`` and ``seed`` stay
# free, so every reachable k is still drawn.

#: 1-D lengths: a 1-element array, odd sizes and an even one
PROP_LENGTHS = (1, 7, 33, 48)
#: (rows, width, br, bw): a 1-element array with a 1x1 block, odd sizes with
#: blocks that do not divide them (the padding crop), a block larger than the
#: array, 1x1 blocks, and blocks that divide evenly
PROP_BLOCKINGS = (
    (1, 1, 1, 1),
    (5, 7, 2, 3),
    (12, 23, 5, 7),
    (3, 5, 8, 16),
    (6, 9, 1, 1),
    (8, 24, 4, 8),
)


@settings(max_examples=80, deadline=None)
@given(
    e=st.integers(-14, 28),  # magnitude exponent: drives the chosen k over 0..FX
    n=st.sampled_from(PROP_LENGTHS),
    seed=st.integers(0, 2**16),
)
def test_prop_roundtrip_is_quantize_at_chosen_k(e, n, seed):
    rng = np.random.default_rng(seed)
    x = (rng.uniform(-1.0, 1.0, n) * 2.0**e).astype(np.float32)
    pa = pack_array(x, FMT)
    assert pa.payload.dtype == payload_dtype(FMT)
    k = int(np.asarray(pa.k).max())
    expect = np.asarray(
        quantize_em(x, FMT.eb + k, FMT.mb + FMT.fx - k), np.float32
    )
    np.testing.assert_array_equal(np.asarray(unpack_array(pa), np.float32), expect)
    # the chosen split is block_storage_k's answer
    assert k == int(np.asarray(block_storage_k(x.reshape(1, -1), FMT)))


@settings(max_examples=60, deadline=None)
@given(
    e=st.integers(-12, 24),
    blocking=st.sampled_from(PROP_BLOCKINGS),
    seed=st.integers(0, 2**16),
)
def test_prop_blocked_roundtrip_and_padding_crop(e, blocking, seed):
    """Per-block splits + non-dividing blocks: pad is cropped, every block
    decodes to its own quantize_em."""
    rows, width, br, bw = blocking
    rng = np.random.default_rng(seed)
    x = (rng.uniform(-1.0, 1.0, (rows, width)) * 2.0**e).astype(np.float32)
    pa = pack_array(x, FMT, block=(br, bw))
    out = np.asarray(unpack_array(pa), np.float32)
    assert out.shape == x.shape
    k = np.asarray(pa.k)
    bR, bW = pa.block
    for i in range(k.shape[0]):
        for j in range(k.shape[1]):
            blk = x[i * bR : (i + 1) * bR, j * bW : (j + 1) * bW]
            kk = int(k[i, j])
            expect = np.asarray(
                quantize_em(blk, FMT.eb + kk, FMT.mb + FMT.fx - kk), np.float32
            )
            np.testing.assert_array_equal(
                out[i * bR : (i + 1) * bR, j * bW : (j + 1) * bW], expect
            )


@settings(max_examples=60, deadline=None)
@given(e=st.integers(-12, 24), seed=st.integers(0, 2**16))
def test_prop_storage_quantize_idempotent(e, seed):
    """quantize -> pack is a projection: a second storage round-trip changes
    nothing (operands bounded away from the round-up-past-max-normal corner,
    where one pack may legitimately overflow to inf — the reason every
    storage path applies exactly ONE pack per boundary)."""
    rng = np.random.default_rng(seed)
    x = (rng.uniform(-0.99, 0.99, 32) * 2.0**e).astype(np.float32)
    once = np.asarray(storage_quantize(x, FMT), np.float32)
    twice = np.asarray(storage_quantize(once, FMT), np.float32)
    np.testing.assert_array_equal(once, twice)


class TestPytree:
    def test_registered_node_survives_jit_and_vmap(self):
        x = np.linspace(-3.0, 3.0, 32, dtype=np.float32)
        pa = pack_array(x, FMT)
        out = jax.jit(lambda p: p)(pa)
        assert isinstance(out, PackedArray)
        np.testing.assert_array_equal(
            np.asarray(unpack_array(out)), np.asarray(unpack_array(pa))
        )
        stacked = jax.tree_util.tree_map(lambda a: jnp.stack([a, a]), pa)
        sliced = jax.tree_util.tree_map(lambda a: a[1], stacked)
        np.testing.assert_array_equal(
            np.asarray(unpack_array(sliced)), np.asarray(unpack_array(pa))
        )

    def test_with_view_round_trips_shapes(self):
        x = np.arange(24, dtype=np.float32).reshape(4, 6) / 7.0
        pa = pack_array(x, FMT)
        flat = pa.with_view((1, 24))
        back = flat.with_view((4, 6))
        np.testing.assert_array_equal(
            np.asarray(unpack_array(back)), np.asarray(unpack_array(pa))
        )

    def test_nbytes_halves_f32(self):
        state = {"u": np.ones((64, 64), np.float32)}
        packed = pack_state(state, FMT)
        assert is_packed(packed) and not is_packed(state)
        assert state_nbytes(packed) < 0.6 * state_nbytes(state)


# -------------------------------------------------------- solver integration


@pytest.mark.parametrize("name", sorted(known_steppers()))
def test_fused_packed_bit_identical_to_quantized(name):
    """The acceptance criterion: packed-state fused runs are bit-identical
    to the f32-carried quantized runs at the same carried splits, on every
    registered stepper (in-kernel packing on the sweep steppers, XLA-boundary
    packing on SWE)."""
    cfg = _small_cfg(name)
    prec = PrecisionConfig(mode="rr_tracked", fmt=FMT)
    steps, every = 8, 4
    runs = {}
    for storage in ("packed", "quantized"):
        sim = Simulation(name, cfg, prec)
        runs[storage] = sim.run(
            steps, snapshot_every=every, execution="fused", storage=storage
        )
    final_p = unpack_state(runs["packed"].state)
    fp, fq = jax.tree_util.tree_leaves(final_p), jax.tree_util.tree_leaves(
        runs["quantized"].state
    )
    for a, b in zip(fp, fq):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(runs["packed"].snapshots), np.asarray(runs["quantized"].snapshots)
    )


def test_reference_plane_packed_matches_quantized():
    cfg = _small_cfg("heat1d")
    prec = PrecisionConfig(mode="rr_tile", fmt=FMT)
    runs = {
        storage: Simulation("heat1d", cfg, prec).run(
            8, snapshot_every=4, execution="reference", storage=storage
        )
        for storage in ("packed", "quantized")
    }
    np.testing.assert_array_equal(
        np.asarray(unpack_state(runs["packed"].state)),
        np.asarray(runs["quantized"].state),
    )
    np.testing.assert_array_equal(
        np.asarray(runs["packed"].snapshots), np.asarray(runs["quantized"].snapshots)
    )


def test_packed_ensemble_carries_packed_state():
    cfg = _small_cfg("heat1d")
    prec = PrecisionConfig(mode="rr_tracked", fmt=FMT)
    sim = Simulation("heat1d", cfg, prec)
    state0 = sim.stepper.init_state(cfg)
    batch = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, 0.5 * x, 2.0 * x]), state0
    )
    res = sim.run_ensemble(batch, 8, snapshot_every=4, storage="packed")
    assert is_packed(res.state)
    member = jax.tree_util.tree_map(lambda x: x[1], res.state)
    solo0 = jax.tree_util.tree_map(lambda x: 0.5 * x, state0)
    solo = sim.run(8, snapshot_every=4, state0=solo0, storage="packed")
    np.testing.assert_array_equal(
        np.asarray(unpack_state(member)), np.asarray(unpack_state(solo.state))
    )


# --------------------------------------------------------------- service leg


def test_service_buckets_separate_by_storage():
    from repro.service.request import SimRequest, resolve_request

    r_f32 = resolve_request(1, SimRequest("heat1d", 8, precision="rr_tracked"))
    r_pk = resolve_request(
        2, SimRequest("heat1d", 8, precision="rr_tracked", storage="packed")
    )
    assert r_f32.key != r_pk.key
    assert r_pk.key.storage == "packed"
    assert r_pk.key.short().endswith("/packed")
    assert "/f32" not in r_f32.key.short()  # f32 keys keep the legacy label

    with pytest.raises(ValueError):
        resolve_request(3, SimRequest("heat1d", 8, storage="zstd"))


def test_service_evict_resume_packed_parity():
    """A packed member evicted through repro.ckpt and resumed finishes with
    state + snapshots bit-identical to a solo packed run."""
    from repro.service.request import SimRequest
    from repro.service.scheduler import ServiceConfig, SimService

    with tempfile.TemporaryDirectory() as td:
        svc = SimService(ServiceConfig(ckpt_dir=td))
        h = svc.submit(
            SimRequest(
                "heat1d", 12, precision="rr_tracked", snapshot_every=4,
                storage="packed",
            )
        )
        rid = h.id
        svc._fill()
        svc.pump()  # one chunk in
        rec = svc._requests[rid]
        assert is_packed(rec.state)
        svc.evict(rid)
        assert rec.status == "evicted"
        assert is_packed(rec.templates["state"])  # templates keep the treedef
        svc.resume(rid)
        svc.run_until_idle()
        result = rec.result
        assert result is not None and is_packed(result.state)

        sim = Simulation("heat1d", None, PrecisionConfig(mode="rr_tracked", fmt=FMT))
        solo = sim.run(
            12, snapshot_every=4, execution=rec.key.execution, storage="packed"
        )
        np.testing.assert_array_equal(
            np.asarray(unpack_state(result.state)),
            np.asarray(unpack_state(solo.state)),
        )
        solo_snaps = np.asarray(solo.snapshots)
        for i, snap in enumerate(result.snapshots):
            np.testing.assert_array_equal(np.asarray(snap), solo_snaps[i])
