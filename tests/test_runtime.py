"""Runtime tests: straggler detection (the sweep's watchdog) and the
persistent compile cache's placement."""
import os
import subprocess
import sys

import pytest

from repro.runtime.fault import StepTimer


def test_straggler_detection():
    """Deterministic: drive the rolling window directly (wall-clock sleeps
    are unreliable on a loaded host)."""
    t = StepTimer(window=50, z_thresh=3.0)
    t.window.extend([0.010 + 0.0001 * (i % 3) for i in range(20)])

    class _Clock:
        now = 100.0
    t.start = lambda: setattr(_Clock, "now", 100.0)  # type: ignore
    import time as _time
    orig = _time.perf_counter
    t._t0 = 100.0
    _time.perf_counter = lambda: 100.5  # 0.5 s step vs ~10 ms window
    try:
        dt, straggler = t.stop()
    finally:
        _time.perf_counter = orig
    assert straggler and t.stragglers == 1 and dt > 0.4


_CACHE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.runtime import compile_cache
print(compile_cache.enable())

def cache_placement_probe(x):
    return x * 3 + 1

jax.block_until_ready(jax.jit(cache_placement_probe)(jnp.ones(4)))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """Entry points' persistent cache: JAX_COMPILATION_CACHE_DIR wins
    when set; otherwise the fixed `<checkout>/.jax_cache`."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(root, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    want = str(tmp_path / "cache") if from_env \
        else os.path.join(root, ".jax_cache")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == want
    assert any(f.startswith("jit_cache_placement_probe-")
               for f in os.listdir(want))
