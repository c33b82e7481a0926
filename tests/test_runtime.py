"""Runtime tests: checkpoint roundtrip, fault-tolerant restart (injected
failure), straggler detection, trainer loss decrease, elastic reshard
(subprocess with 8 forced host devices)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config
from repro.runtime import checkpoint as CK
from repro.runtime.fault import StepTimer
from repro.train.trainer import TrainConfig, Trainer


def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                        "nested": {"b": jnp.ones((5,))}},
             "opt": {"step": jnp.int32(7)}}
    CK.save_checkpoint(str(tmp_path), 7, state)
    path = CK.latest_checkpoint(str(tmp_path))
    assert path and path.endswith("step_00000007")
    step, restored = CK.restore_checkpoint(path, state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_latest(tmp_path):
    state = {"x": jnp.zeros(())}
    for s in [1, 2, 3, 4, 5]:
        CK.save_checkpoint(str(tmp_path), s, state, keep=2)
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000004", "step_00000005"]


def test_async_checkpoint(tmp_path):
    state = {"x": jnp.arange(10.0)}
    t = CK.save_checkpoint(str(tmp_path), 3, state, async_save=True)
    t.join()
    assert CK.latest_checkpoint(str(tmp_path))


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


@pytest.mark.slow
def test_trainer_loss_decreases(tmp_path):
    cfg = get_config("xlstm-125m", smoke=True)
    tcfg = TrainConfig(steps=25, batch=4, seq=64, lr=3e-3, log_every=1)
    tr = Trainer(cfg, tcfg)
    tr.run()
    losses = [m["loss"] for m in tr.metrics_log]
    assert losses[-1] < losses[0] * 0.95, losses[:3] + losses[-3:]


@pytest.mark.slow
def test_fault_tolerant_restart(tmp_path):
    """Inject a failure mid-run; the runner must restore from the last
    checkpoint and finish all steps."""
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    tcfg = TrainConfig(steps=12, batch=2, seq=32, ckpt_dir=str(tmp_path),
                       ckpt_every=5, log_every=1)
    tr = Trainer(cfg, tcfg)
    tr.run(fail_at=8)  # dies after the step-5 checkpoint
    assert tr.restarts == 1
    steps_logged = [m["step"] for m in tr.metrics_log]
    assert max(steps_logged) == tcfg.steps - 1


_ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.runtime import checkpoint as CK
from repro.runtime.elastic import choose_mesh, reshard_restore

tmp = sys.argv[1]
state = {"params": {"w": jnp.arange(64.0).reshape(8, 8),
                    "emb": jnp.arange(32.0).reshape(16, 2)},
         "opt": {"m": {"w": jnp.zeros((8, 8)), "emb": jnp.zeros((16, 2))}}}
# save from an 8-device (4,2) mesh
mesh_a = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
with mesh_a:
    sharded = jax.device_put(state, NamedSharding(mesh_a, P()))
CK.save_checkpoint(tmp, 1, sharded)
# restore onto a (2,2) 4-device mesh
mesh_b = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
step, restored = reshard_restore(CK.latest_checkpoint(tmp), state, mesh_b)
ok = bool(jnp.all(restored["params"]["w"] == state["params"]["w"]))
n_shards = len(restored["params"]["w"].sharding.device_set)
print(json.dumps({"ok": ok, "step": step, "n_shards": n_shards}))
"""


def test_elastic_reshard_subprocess(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT,
                          str(tmp_path)], capture_output=True, text=True,
                         env=env, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["step"] == 1


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
