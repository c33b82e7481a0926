"""Shared pytest config.  NOTE: deliberately no XLA_FLAGS here — smoke tests
and benches must see the single real device (subprocess tests force their
own counts)."""
import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Large compiled programs (worksteal sims, engine grids) accumulate
    LLVM JIT memory; drop them when a module finishes."""
    yield
    jax.clear_caches()
