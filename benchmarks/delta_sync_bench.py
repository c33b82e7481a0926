"""Framework-layer benchmark: sRSP-style selective cross-pod delta sync vs
full all-reduce, on banks with asymmetric update sparsity (MoE expert banks,
embedding rows).  Reports bytes moved + wall time on a simulated pod axis.

Run inside a process with forced host devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m benchmarks.delta_sync_bench
(benchmarks/run.py spawns it that way.)"""
from __future__ import annotations

import json
import os
import sys
import time


def main():
    from repro.runtime import compile_cache
    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.distributed.hier_sync import bank_init, make_pod_sync

    n_pods = min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n_pods]).reshape(n_pods), ("pod",))
    rng = np.random.default_rng(0)
    rows = []
    for (nb, bs, frac_dirty, label) in [
            (256, 2048, 0.03, "moe_expert_bank"),     # ~granite expert FFN
            (1024, 1024, 0.02, "embedding_rows"),
            (256, 2048, 0.50, "dense_layer(worst)"),
    ]:
        base = rng.normal(size=(nb, bs)).astype(np.float32)
        banks = np.broadcast_to(base, (n_pods, nb, bs)).copy()
        for pod in range(n_pods):
            k = max(1, int(nb * frac_dirty))
            idx = rng.choice(nb, size=k, replace=False)
            banks[pod, idx] += 0.01 * rng.normal(size=(k, bs))
        max_dirty = max(8, int(nb * frac_dirty * n_pods * 2))
        st = jax.vmap(bank_init)(jnp.asarray(
            np.broadcast_to(base, (n_pods, nb, bs)).copy()))
        sh = lambda x: jax.device_put(x, NamedSharding(
            mesh, P(*(("pod",) + (None,) * (x.ndim - 1)))))
        banks_j = sh(jnp.asarray(banks))
        st = jax.tree.map(sh, st)
        out = {"bank": label, "n_blocks": nb, "block": bs,
               "dirty_frac": frac_dirty}
        for mode, selective in (("srsp_selective", True), ("full_ar", False)):
            sync = make_pod_sync(mesh, nb, bs, max_dirty=max_dirty,
                                 selective=selective)
            nbk, nst = sync(banks_j, st)          # compile+run
            jax.block_until_ready(nbk)
            t0 = time.perf_counter()
            for _ in range(5):
                nbk, nst2 = sync(banks_j, st)
            jax.block_until_ready(nbk)
            dt = (time.perf_counter() - t0) / 5
            moved = float(np.asarray(nst.bytes_selective)[0])
            out[f"{mode}_bytes"] = moved
            out[f"{mode}_us"] = dt * 1e6
        out["bytes_ratio"] = out["srsp_selective_bytes"] / out["full_ar_bytes"]
        rows.append(out)
        print(f"  {label:22s} dirty={frac_dirty:4.0%} "
              f"selective={out['srsp_selective_bytes']/2**20:8.2f}MiB "
              f"full={out['full_ar_bytes']/2**20:8.2f}MiB "
              f"ratio={out['bytes_ratio']:.3f}", flush=True)
    os.makedirs("artifacts/paper", exist_ok=True)
    json.dump(rows, open("artifacts/paper/delta_sync.json", "w"), indent=1)


if __name__ == "__main__":
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
    main()
