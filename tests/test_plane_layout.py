"""The lane-dense metadata planes against their boolean view (DESIGN.md §8).

`wvalid`/`wdirty` are stored `[n_caches, n_blocks * L]`: lane `w` of block
`b` is column `b * L + w`, with L = ceil(W/32) packed words or W boolean
flags.  Each case runs in both layouts (the module flag `P.PACKED` is
switched for the test) and at W=16 (packed L=1) and W=64 (packed L=2),
and checks every layout-aware helper through `wvalid_bool`/`wdirty_bool`
against a plain numpy model of the boolean flags.  The benchmark only
runs L=1, so these cases are what guard L>1.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitmask
from repro.core import protocol as P

N, NB = 4, 6


@pytest.fixture(params=["packed", "bool"])
def packed(request, monkeypatch):
    monkeypatch.setattr(P, "PACKED", request.param == "packed")
    return P.PACKED


def _store(W, flags_v, flags_d):
    """A fresh store whose planes hold the boolean flags [N, NB, W]."""
    cfg = P.ProtoConfig(n_caches=N, n_words=NB * W, block_words=W)
    st = P.make_store(cfg)
    L = cfg.meta_lanes

    def plane(flags):
        flags = jnp.asarray(flags)
        rows = bitmask.pack(flags) if P.PACKED else flags
        return rows.reshape(N, NB * L)
    return cfg, st._replace(wvalid=plane(flags_v), wdirty=plane(flags_d))


def _targets(rng, W):
    """One (block, offset) per cache lane."""
    return (rng.integers(0, NB, size=N).astype(np.int32),
            rng.integers(0, W, size=N).astype(np.int32))


@pytest.mark.parametrize("W", [16, 64])
def test_store_planes_are_lane_dense(packed, W):
    cfg = P.ProtoConfig(n_caches=N, n_words=NB * W, block_words=W)
    st = P.make_store(cfg)
    L = (W + 31) // 32 if packed else W
    assert cfg.meta_lanes == L
    for plane in (st.wvalid, st.wdirty):
        assert plane.shape == (N, NB * L)
        assert plane.dtype == (jnp.uint32 if packed else jnp.bool_)
    assert P.wvalid_bool(st).shape == (N, NB, W)


@pytest.mark.parametrize("W", [16, 64])
def test_pl_get_and_clear_match_bool_view(packed, W):
    rng = np.random.default_rng(W)
    fv = rng.random((N, NB, W)) < 0.5
    fd = rng.random((N, NB, W)) < 0.5
    cfg, st = _store(W, fv, fd)
    np.testing.assert_array_equal(np.asarray(P.wvalid_bool(st)), fv)
    np.testing.assert_array_equal(np.asarray(P.wdirty_bool(st)), fd)
    lane = np.arange(N)
    b, o = _targets(rng, W)
    got = P._pl_get(cfg, st.wvalid, jnp.asarray(lane), b, o)
    np.testing.assert_array_equal(np.asarray(got), fv[lane, b, o])
    off = np.array([True, False, True, True])
    wd = P._pl_clear(cfg, st.wdirty, jnp.asarray(lane), b, o, off)
    want = fd.copy()
    want[lane[off], b[off], o[off]] = False
    np.testing.assert_array_equal(
        np.asarray(P.wdirty_bool(st._replace(wdirty=wd))), want)


@pytest.mark.parametrize("W", [16, 64])
def test_plane_scatter_set_matches_bool_view(packed, W):
    rng = np.random.default_rng(3 * W)
    fv = rng.random((N, NB, W)) < 0.3
    cfg, st = _store(W, fv, np.zeros_like(fv))
    # distinct (lane, block, offset) triples; the last two fall past the
    # store and must drop
    flat = rng.choice(N * NB * W, size=10, replace=False)
    lane, b, o = np.unravel_index(flat, (N, NB, W))
    b = b.copy()
    b[-2:] = NB
    wv = P.plane_scatter_set(cfg, st.wvalid, jnp.asarray(lane),
                             jnp.asarray(b), jnp.asarray(o))
    want = fv.copy()
    want[lane[:-2], b[:-2], o[:-2]] = True
    np.testing.assert_array_equal(
        np.asarray(P.wvalid_bool(st._replace(wvalid=wv))), want)


@pytest.mark.parametrize("W", [16, 64])
def test_row_gather_and_scatter_match_bool_view(packed, W):
    rng = np.random.default_rng(5 * W)
    fd = rng.random((N, NB, W)) < 0.5
    cfg, st = _store(W, np.zeros_like(fd), fd)
    # the b_drain shape: [n, cap] (cache, block) pairs, distinct per cache
    crow = np.repeat(np.arange(N)[:, None], 3, axis=1)
    blks = np.stack([rng.choice(NB, size=3, replace=False)
                     for _ in range(N)]).astype(np.int32)
    rows = P._rows_get(cfg, st.wdirty, jnp.asarray(crow), jnp.asarray(blks))
    assert rows.shape == (N, 3, cfg.meta_lanes)
    view = bitmask.unpack(rows, W) if packed else rows
    np.testing.assert_array_equal(np.asarray(view), fd[crow, blks])
    # write cleared rows back; entries at block NB drop
    idx = blks.copy()
    idx[:, 0] = NB
    wd = P._rows_put(cfg, st.wdirty, jnp.asarray(crow), jnp.asarray(idx),
                     jnp.zeros_like(rows))
    want = fd.copy()
    want[crow[:, 1:], blks[:, 1:]] = False
    np.testing.assert_array_equal(
        np.asarray(P.wdirty_bool(st._replace(wdirty=wd))), want)


@pytest.mark.parametrize("W", [16, 64])
def test_drain_and_writeback_clear_dirty_rows(packed, W):
    """Stores through `b_store_word`, one `b_writeback` and one `b_drain`
    of a cache subset: the dirty view and L2 follow the boolean model."""
    rng = np.random.default_rng(7 * W)
    cfg = P.ProtoConfig(n_caches=N, n_words=NB * W, block_words=W)
    st = P.make_store(cfg)
    dirty = np.zeros((N, NB, W), bool)
    l2 = np.zeros((NB, W), np.int32)
    written = {}
    # every cache writes its own words (disjoint across caches)
    for step in range(6):
        addrs = np.array([(step % NB) * W + rng.integers(0, W // N) * N + i
                          for i in range(N)], np.int32)
        vals = rng.integers(1, 1000, size=N).astype(np.int32)
        st, _ = P.b_store_word(cfg, st, jnp.ones((N,), bool), addrs, vals)
        for i in range(N):
            b, o = divmod(int(addrs[i]), W)
            dirty[i, b, o] = True
            written[(i, b, o)] = int(vals[i])
    np.testing.assert_array_equal(np.asarray(P.wdirty_bool(st)), dirty)
    np.testing.assert_array_equal(np.asarray(P.wvalid_bool(st)), dirty)

    def flush(i, b):
        for o in np.nonzero(dirty[i, b])[0]:
            l2[b, o] = written[(i, b, o)]
        dirty[i, b] = False

    # cache 0 writes back block 1 alone
    st, did = P.b_writeback(cfg, st, np.array([1, -1, -1, -1], np.int32),
                            np.array([True, False, False, False]))
    assert float(did[0]) == float(dirty[0, 1].any())
    flush(0, 1)
    np.testing.assert_array_equal(np.asarray(P.wdirty_bool(st)), dirty)
    # caches 1 and 3 drain their whole sFIFO
    mask = np.array([False, True, False, True])
    st, n_wb = P.b_drain(cfg, st, np.where(mask, P.DRAIN_ALL, P.INVALID),
                         mask)
    for i in np.nonzero(mask)[0]:
        assert float(n_wb[i]) == float(dirty[i].any(axis=-1).sum())
        for b in range(NB):
            flush(i, b)
    np.testing.assert_array_equal(np.asarray(P.wdirty_bool(st)), dirty)
    np.testing.assert_array_equal(np.asarray(st.l2), l2)
