"""Compile the sweep path's Pallas kernels for a described TPU v5e.

Interpret mode cannot see the TPU's block-tiling and lowering rules; the
TPU compiler, installed with JAX, can — for a chip that is described
rather than attached.  Each case compiles one kernel at the smoke's
shapes (n agents, W=16 words, the packed L=1 layout, two blocks per
agent, a 16-entry sFIFO) and checks the compiled program holds the
kernel.  Nothing here runs: results are pinned by the interpret-mode
suites (tests/test_kernels.py, tests/test_engine_equivalence.py).

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest

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
        plane = ((n, 2 * n, 1), jnp.uint32) if packed \
            else ((n, 2 * n, W), jnp.bool_)
        return plane_commit_pallas, [plane, plane, ((n,), jnp.int32),
                                     ((n,), jnp.int32), ((n,), jnp.bool_),
                                     ((n,), jnp.bool_)]
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
