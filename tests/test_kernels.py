"""Per-kernel validation: shape/dtype sweeps, interpret=True vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.selective_flush import selective_flush, selective_apply
from repro.kernels.selective_flush.ref import (selective_flush_ref,
                                               selective_apply_ref)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("nb,bs,nd", [(16, 128, 4), (64, 256, 16),
                                      (128, 512, 32), (8, 128, 8)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_selective_flush_sweep(nb, bs, nd, dtype):
    bank = jnp.asarray(RNG.normal(size=(nb, bs)).astype(np.float32)).astype(dtype)
    idx = jnp.asarray(RNG.integers(-1, nb, size=nd).astype(np.int32))
    out = selective_flush(bank, idx)
    ref = selective_flush_ref(bank, idx)
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(ref.astype(jnp.float32)))


def test_selective_apply_roundtrip():
    bank = jnp.asarray(RNG.normal(size=(32, 64)).astype(np.float32))
    idx = jnp.asarray(np.array([3, 7, -1, 30], np.int32))
    flushed = selective_flush(bank, idx)
    restored = selective_apply(jnp.zeros_like(bank), flushed, idx)
    for i in [3, 7, 30]:
        np.testing.assert_array_equal(np.asarray(restored[i]),
                                      np.asarray(bank[i]))
    assert float(jnp.abs(restored).sum()) == pytest.approx(
        float(jnp.abs(bank[jnp.asarray([3, 7, 30])]).sum()), rel=1e-6)


# --------------------------------------------------------------------------
# fused-turn megakernel (DESIGN.md §12): interpret=True vs jnp oracle
# --------------------------------------------------------------------------

def _plan_inputs(n, *, tie_every=3, seed=1):
    rng = np.random.default_rng(seed)
    # small-integer clocks force ties (the lex order's hard case)
    clocks = jnp.asarray((rng.integers(0, max(2, n // tie_every),
                                       size=n)).astype(np.float32))
    can_l = jnp.asarray(rng.random(n) < 0.6)
    can_r = jnp.asarray(rng.random(n) < 0.4)
    bound = jnp.asarray(rng.integers(1, 5, size=n).astype(np.float32))
    raddr = jnp.asarray(rng.integers(0, max(2, n // 4), size=n)
                        .astype(np.int32))
    return clocks, can_l, can_r, bound, raddr


@pytest.mark.parametrize("n", [8, 64])
@pytest.mark.parametrize("remote_cap", [True, False])
@pytest.mark.parametrize("fenced", [True, False])
def test_trip_plan_kernel_matches_ref(n, remote_cap, fenced):
    from repro.kernels.fused_turn.kernel import trip_plan_pallas
    from repro.kernels.fused_turn.ref import BIG, trip_plan_ref
    clocks, can_l, can_r, bound, raddr = _plan_inputs(n)
    horizon = jnp.float32(float(np.median(np.asarray(clocks)))) \
        if fenced else None
    want = trip_plan_ref(clocks, can_l, can_r, bound,
                         raddr if remote_cap else None, horizon)
    got = trip_plan_pallas(clocks, can_l, can_r, bound, raddr,
                           BIG if horizon is None else horizon,
                           remote_cap=remote_cap, interpret=True)
    np.testing.assert_array_equal(np.asarray(got.lmask),
                                  np.asarray(want.lmask))
    np.testing.assert_array_equal(np.asarray(got.rmask),
                                  np.asarray(want.rmask))
    assert int(got.wg) == int(want.wg)


def test_trip_plan_kernel_empty_candidates():
    """No capable lane: lmask/rmask all-False and wg falls to 0 (matching
    jnp.argmin over an all-BIG row)."""
    from repro.kernels.fused_turn.kernel import trip_plan_pallas
    from repro.kernels.fused_turn.ref import BIG
    n = 8
    z = jnp.zeros((n,), bool)
    got = trip_plan_pallas(jnp.arange(n, dtype=jnp.float32), z, z,
                           jnp.ones((n,), jnp.float32),
                           jnp.zeros((n,), jnp.int32), BIG,
                           remote_cap=True, interpret=True)
    assert not bool(jnp.any(got.lmask)) and not bool(jnp.any(got.rmask))
    assert int(got.wg) == 0


def test_trip_plan_serial_fallback_is_one_hot():
    """Batch empty via a tight horizon, first argmin lane local-capable:
    lmask must be exactly one_hot(wg) — the folded serial-local case."""
    from repro.kernels.fused_turn.kernel import trip_plan_pallas
    from repro.kernels.fused_turn.ref import trip_plan_ref
    clocks = jnp.asarray(np.array([5.0, 2.0, 7.0, 2.0], np.float32))
    can_l = jnp.asarray(np.array([True, True, True, True]))
    can_r = jnp.asarray(np.array([False, False, True, False]))
    bound = jnp.ones((4,), jnp.float32)
    horizon = jnp.float32(0.0)   # fences out every batch lane
    want = trip_plan_ref(clocks, can_l, can_r, bound, None, horizon)
    got = trip_plan_pallas(clocks, can_l, can_r, bound,
                           jnp.zeros((4,), jnp.int32), horizon,
                           remote_cap=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(got.lmask),
                                  np.asarray(want.lmask))
    assert int(got.wg) == 1 and np.asarray(want.lmask).sum() == 1
    assert bool(want.lmask[1])


@pytest.mark.parametrize("nb,W", [(4, 16), (8, 40)])   # L=1 and ragged L=2
def test_plane_commit_kernel_matches_ref(nb, W):
    from repro.core import bitmask
    from repro.kernels.fused_turn.kernel import plane_commit_pallas
    from repro.kernels.fused_turn.ref import plane_commit_ref
    rng = np.random.default_rng(7)
    n, L = 6, (W + 31) // 32
    # lane-dense [n, nb * L] planes
    wv = jnp.asarray(rng.integers(0, 2**32, size=(n, nb * L),
                                  dtype=np.uint64).astype(np.uint32))
    wd = jnp.asarray(rng.integers(0, 2**32, size=(n, nb * L),
                                  dtype=np.uint64).astype(np.uint32))
    b = jnp.asarray(rng.integers(0, nb, size=n).astype(np.int32))
    o = jnp.asarray(rng.integers(0, W, size=n).astype(np.int32))
    sv = jnp.asarray(rng.random(n) < 0.7)
    sd = jnp.asarray(rng.random(n) < 0.5)
    want = plane_commit_ref(wv, wd, b, o, sv, sd, L)
    got = plane_commit_pallas(wv, wd, b, o, sv, sd, lanes=L, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # cross-check against the boolean-layout reference (W columns per
    # block) through unpack
    unpack = lambda p: np.asarray(  # noqa: E731
        bitmask.unpack(jnp.asarray(p).reshape(n, nb, L), W))
    wvb, wdb = jnp.asarray(unpack(wv)), jnp.asarray(unpack(wd))
    wantb = plane_commit_ref(wvb.reshape(n, nb * W), wdb.reshape(n, nb * W),
                             b, o, sv, sd, W)
    flat = lambda p: np.asarray(p).reshape(n, nb, W)  # noqa: E731
    np.testing.assert_array_equal(unpack(got[0]), flat(wantb[0]))
    np.testing.assert_array_equal(unpack(got[1]), flat(wantb[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(wantb[2]))
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(wantb[3]))
    # the boolean layout through the kernel
    gotb = plane_commit_pallas(wvb.reshape(n, nb * W), wdb.reshape(n, nb * W),
                               b, o, sv, sd, lanes=W, interpret=True)
    for g, w in zip(gotb, wantb):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_plane_commit_load_shape_skips_dirty():
    """set_dirty=None (the b_load call shape) must leave wdirty untouched
    and still report the pre-op bits of BOTH planes."""
    from repro.kernels.fused_turn.ref import plane_commit_ref
    rng = np.random.default_rng(9)
    n, nb, L = 4, 4, 1
    wv = jnp.asarray(rng.integers(0, 2**32, size=(n, nb * L),
                                  dtype=np.uint64).astype(np.uint32))
    wd = jnp.asarray(rng.integers(0, 2**32, size=(n, nb * L),
                                  dtype=np.uint64).astype(np.uint32))
    b = jnp.asarray(np.array([0, 1, 2, 3], np.int32))
    o = jnp.asarray(np.array([0, 5, 13, 15], np.int32))
    sv = jnp.asarray(np.array([True, False, True, True]))
    wv2, wd2, wasv, wasd = plane_commit_ref(wv, wd, b, o, sv, None, L)
    np.testing.assert_array_equal(np.asarray(wd2), np.asarray(wd))
    lane = np.arange(n)
    col = np.asarray(b) * L + (np.asarray(o) >> 5)
    bit = np.uint32(1) << (np.asarray(o) & 31)
    np.testing.assert_array_equal(
        np.asarray(wasv), (np.asarray(wv)[lane, col] & bit) != 0)
    np.testing.assert_array_equal(
        np.asarray(wasd), (np.asarray(wd)[lane, col] & bit) != 0)
