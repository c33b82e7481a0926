"""Quickstart: the paper's mechanism in 40 lines.

Drive the sRSP protocol directly (local release -> remote acquire ->
selective flush) and watch the cost counters.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp

from repro.core import protocol as P
from repro.core.costmodel import makespan

cfg = P.ProtoConfig(n_caches=8, n_words=512)
store = P.make_store(cfg)

LOCK, DATA = jnp.int32(64), jnp.int32(5)

# work-group 0 (the LOCAL SHARER) updates shared data and releases locally —
# cheap, L1-only, tracked by sFIFO + LR-TBL
store, _ = P.store_word(cfg, store, 0, DATA, 42)
store = P.local_release(cfg, store, 0, LOCK, 0)
print(f"after local release:  makespan={float(makespan(store.counters)):6.0f} "
      f"l2_accesses={float(store.counters.l2_accesses):4.0f}")

# work-group 5 (a REMOTE SHARER / work-stealer) acquires remotely: sRSP
# probes LR-TBLs, selectively flushes ONLY wg0's dirty blocks, and promotes
store, old = P.srsp_remote_acquire(cfg, store, 5, LOCK, 0, 1)
store, val = P.load(cfg, store, 5, DATA)
print(f"after remote acquire: stolen value={int(val)} (expect 42), "
      f"flushed_blocks={float(store.counters.wb_blocks):3.0f}, "
      f"full_invalidations={float(store.counters.inv_full):3.0f}")

store = P.srsp_remote_release(cfg, store, 5, LOCK, 0)
# wg0's NEXT local acquire is promoted (PA-TBL hit) — and only that one
store, _ = P.local_acquire(cfg, store, 0, LOCK, 0, 1)
print(f"promotions={float(store.counters.promotions):3.0f} (exactly 1: "
      f"selectivity per address)")
