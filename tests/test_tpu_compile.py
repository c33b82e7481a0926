"""Compile the sweep path's Pallas kernels for a described TPU v5e.

Interpret mode cannot see the TPU's block-tiling and lowering rules; the
TPU compiler, installed with JAX, can — for a chip that is described
rather than attached.  Each kernel case compiles one kernel at the
smoke's shapes (n agents, W=16 words, the packed L=1 layout, two blocks
per agent, a 16-entry sFIFO) and checks the compiled program holds the
kernel.  The engine case compiles a whole work-steal engine with its
kernels and checks the layouts the compiler gave the metadata planes.
Nothing here runs: results are pinned by the interpret-mode suites
(tests/test_kernels.py, tests/test_engine_equivalence.py).

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import common
from repro.kernels.fused_turn.kernel import (plane_commit_pallas,
                                             trip_plan_pallas)
from repro.kernels.selective_flush.kernel import drain_writeback_pallas

W, CAP, REPLICAS = 16, 16, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without the chip: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _drain(packed):
    def case(n):
        nb, m = 2 * n, n * CAP
        dirty = ((m, 1), jnp.uint32) if packed else ((m, W), jnp.bool_)
        return drain_writeback_pallas, [((nb, W), jnp.int32),
                                        ((m, W), jnp.int32), dirty,
                                        ((m,), jnp.int32)]
    return case


def _plan(remote_cap):
    def case(n):
        f = lambda *a: trip_plan_pallas(*a, remote_cap=remote_cap)  # noqa: E731
        return f, [((n,), jnp.float32), ((n,), jnp.bool_), ((n,), jnp.bool_),
                   ((n,), jnp.float32), ((n,), jnp.int32),
                   ((), jnp.float32)]
    return case


def _commit(packed):
    def case(n):
        lanes = 1 if packed else W
        plane = ((n, 2 * n * lanes), jnp.uint32 if packed else jnp.bool_)
        f = lambda *a: plane_commit_pallas(*a, lanes=lanes)  # noqa: E731
        return f, [plane, plane, ((n,), jnp.int32), ((n,), jnp.int32),
                   ((n,), jnp.bool_), ((n,), jnp.bool_)]
    return case


CASES = {"drain_packed": _drain(True), "drain_bool": _drain(False),
         "plan_remote_cap": _plan(True), "plan_local_only": _plan(False),
         "commit_packed": _commit(True), "commit_bool": _commit(False)}


@pytest.mark.parametrize("vmapped,n", [(False, 16), (False, 64),
                                       (True, 64)])
@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel, n, vmapped):
    """`vmapped` compiles the kernel under jax.vmap over a replica axis,
    the way `harness.run_*_many` calls it."""
    fn, shapes = CASES[kernel](n)
    if vmapped:
        fn = jax.vmap(fn)
        shapes = [((REPLICAS,) + s, dt) for s, dt in shapes]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# an HLO array type with a tiled layout: dtype[dims]{minor_to_major:T(...
_TILED = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]+)\]\{([0-9,]+):T\(")


def _padded_planes(text, n_elems):
    """Arrays of `n_elems` elements whose tiled layout puts an axis of
    extent 1 minor-most: the tile pads that one word to 128."""
    bad = []
    for m in _TILED.finditer(text):
        dims = [int(d) for d in m.group(1).split(",")]
        minor = int(m.group(2).split(",")[0])
        size = 1
        for d in dims:
            size *= d
        if size == n_elems and dims[minor] == 1:
            bad.append(m.group(0))
    return bad


@pytest.mark.parametrize("scenario", ["srsp", "rsp"])
def test_worksteal_engine_planes_stay_unpadded(one_chip, scenario,
                                               monkeypatch):
    """The batched work-steal engine at n=64 with 512 queue slots (a
    64 x 3,136 plane) compiled with its Pallas kernels, as a chip runs it:
    no plane-sized array may take a layout that tiles an extent-1 minor
    axis, which relays the whole plane 128x padded on every remote op."""
    from repro.core import protocol as P
    from repro.workloads import harness, worksteal

    ws = worksteal.WSConfig(n_wgs=64, n_chunks_max=512)
    n, m = ws.n_wgs, ws.n_chunks_max
    proto = harness.resolve_proto(scenario)
    wl = worksteal.build_workload(ws, proto, worksteal.SCENARIOS[scenario][1])
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                          sharding=one_chip)
    store = jax.tree.map(spec, jax.eval_shape(
        lambda: P.make_store(ws.proto_cfg())))
    arr = lambda dt, *s: jax.ShapeDtypeStruct(s, dt,  # noqa: E731
                                              sharding=one_chip)
    state = worksteal.SimState(store=store, qsize=arr(jnp.int32, n),
                               processed=arr(jnp.int32, m),
                               last_inv=arr(jnp.float32, n),
                               rounds=arr(jnp.int32),
                               rem=arr(jnp.float32, n))
    # trace with the kernels a chip takes; traces cached in ref mode are
    # dropped before, and the Pallas ones after
    monkeypatch.setattr(common, "kernel_mode", lambda: "pallas")
    jax.clear_caches()
    try:
        text = harness.runner("batched").lower(
            wl, state, arr(jnp.int32, m), arr(jnp.float32, m)
        ).compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in text
    plane = store.wvalid.shape
    assert len(plane) == 2 and plane[0] == n
    assert _padded_planes(text, plane[0] * plane[1]) == []
