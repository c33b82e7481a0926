"""Protocol-engine benchmark: serial vs batched work-steal engine.

Measures, per app x scenario x n_wgs, for each engine:
  * compile_s            first-call wall time (jit compile + first iteration)
  * steady_s_per_iter    mean wall time of subsequent simulator iterations
  * events_per_iter      scheduler turns executed per iteration
  * events_per_s         events_per_iter / steady_s_per_iter
and emits BENCH_protocol_engine.json, including batched-vs-serial speedups.

Seed-engine baseline: pass --seed-src <path-to-seed-checkout>/src (e.g. a
`git worktree add seed-tree <seed-commit>` of the pre-refactor engine) and
the same measurement runs against the old scan-based engine in a
subprocess; speedup_vs_seed fields are then filled in.  The JSON committed
with the refactor PR was produced this way against commit 9810f7e.

Each configuration runs as its own top-level child process; this parent
never imports JAX, so every child can take the device (a process that has
touched JAX holds the chip, and a child then cannot reach it).  The
children share the persistent compilation cache: JAX_COMPILATION_CACHE_DIR
if set, else `<checkout>/.jax_cache` (repro.runtime.compile_cache).

Usage:
  PYTHONPATH=src python benchmarks/protocol_engine_bench.py \
      [--apps pagerank] [--scenarios srsp rsp] [--sizes 16 64 256] \
      [--iters 4] [--seed-src seed-tree/src] [--out BENCH_protocol_engine.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.runtime import compile_cache  # noqa: E402  (imports no JAX)


# shape of one benchmark configuration, shared with the seed subprocess
def bench_config(n_wgs: int):
    n_chunks = max(2 * n_wgs, 64)
    graph_n = 32 * (n_chunks // 2)      # half-full queues: steals happen
    return n_chunks, graph_n


_MEASURE_SNIPPET = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from repro.data.graphs import collab_like
from repro.workloads.worksteal import WorkStealSim, WSConfig, SimState

app, scenario, n_wgs, n_chunks, graph_n, iters, engine = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), sys.argv[7])

ws = WSConfig(n_wgs=n_wgs, chunk_cap=32, n_chunks_max=n_chunks)
g = collab_like(n=graph_n, m=4, seed=2)
sim = (WorkStealSim(ws, scenario) if engine == "seed"
       else WorkStealSim(ws, scenario, engine))
store = sim.make_store()
last_inv = jnp.zeros((ws.n_wgs,), jnp.float32)
frontier = np.arange(g.n, dtype=np.int32)

errors = 0
t0 = time.perf_counter()
store, last_inv, e, _ = sim.run_iteration(store, frontier, g.degrees, last_inv)
jax.block_until_ready(store.counters.cycles)
compile_s = time.perf_counter() - t0
errors += e

times = []
for _ in range(iters):
    t0 = time.perf_counter()
    store, last_inv, e, _ = sim.run_iteration(store, frontier, g.degrees,
                                              last_inv)
    jax.block_until_ready(store.counters.cycles)
    times.append(time.perf_counter() - t0)
    errors += e

# scheduler turns: every pop/steal turn is one acquire+release pair; the
# per-iteration batched enqueue contributes one pair per work-group, which
# is setup, not a round-loop turn — subtract it
c = store.counters
sync_pairs = float(c.local_syncs + c.remote_syncs + c.global_syncs) / 2.0
events = sync_pairs - n_wgs * (iters + 1)
steady = float(np.mean(times))
print(json.dumps({
    "app": app, "scenario": scenario, "n_wgs": n_wgs, "engine": engine,
    "n_chunks": n_chunks, "graph_n": graph_n, "iters_timed": iters,
    "compile_s": round(compile_s, 4),
    "steady_s_per_iter": round(steady, 5),
    "events_total": events,
    "events_per_iter": round(events / (iters + 1), 1),
    "events_per_s": round(events / (iters + 1) / steady, 1),
    "proc_errors": errors,
    "makespan": float(jnp.max(c.cycles)),
    "backend": jax.default_backend(),
}))
"""


def measure(app, scenario, n_wgs, iters, engine, seed_src=None):
    """Run one config in a subprocess (isolates jit caches and lets the
    seed engine import from an old checkout)."""
    n_chunks, graph_n = bench_config(n_wgs)
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = seed_src if engine == "seed" else os.path.join(root, "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache.DEFAULT_DIR)
    out = subprocess.run(
        [sys.executable, "-c", _MEASURE_SNIPPET, app, scenario, str(n_wgs),
         str(n_chunks), str(graph_n), str(iters), engine],
        capture_output=True, text=True, env=env)
    if out.returncode != 0:
        print(out.stderr[-2000:], file=sys.stderr)
        raise RuntimeError(f"bench subprocess failed: {app}/{scenario}/"
                           f"{n_wgs}/{engine}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--apps", nargs="+", default=["pagerank"])
    ap.add_argument("--scenarios", nargs="+", default=["srsp", "rsp"])
    ap.add_argument("--sizes", nargs="+", type=int, default=[16, 64, 256])
    ap.add_argument("--engines", nargs="+", default=["batched", "serial"])
    ap.add_argument("--iters", type=int, default=4,
                    help="steady-state iterations per config (halved for "
                         "n_wgs >= 256)")
    ap.add_argument("--seed-src", default=None,
                    help="path to a pre-refactor checkout's src/ to measure "
                         "the seed engine baseline live")
    ap.add_argument("--serial-max-wgs", type=int, default=128,
                    help="skip serial/seed engines above this n_wgs (the "
                         "scan-serialized engines take minutes per iteration "
                         "there — the scaling wall this bench documents)")
    ap.add_argument("--out", default="BENCH_protocol_engine.json")
    args = ap.parse_args()

    engines = list(args.engines)
    if args.seed_src:
        engines.append("seed")

    runs = []
    for app in args.apps:
        for scen in args.scenarios:
            for n_wgs in args.sizes:
                iters = max(1, args.iters // 2) if n_wgs >= 256 else args.iters
                for engine in engines:
                    if engine != "batched" and n_wgs > args.serial_max_wgs:
                        print(f"{app}/{scen}/n_wgs={n_wgs}/{engine}: skipped "
                              f"(--serial-max-wgs {args.serial_max_wgs}; "
                              f"measured 43.8 s/iter for serial at 256 — "
                              f"beyond the old engine's reach)", flush=True)
                        continue
                    t0 = time.perf_counter()
                    rec = measure(app, scen, n_wgs, iters, engine,
                                  args.seed_src)
                    rec["bench_wall_s"] = round(time.perf_counter() - t0, 2)
                    runs.append(rec)
                    print(f"{app}/{scen}/n_wgs={n_wgs}/{engine}: "
                          f"compile={rec['compile_s']:.2f}s "
                          f"steady={rec['steady_s_per_iter'] * 1e3:.1f}ms/iter "
                          f"events/s={rec['events_per_s']:.0f} "
                          f"errors={rec['proc_errors']}", flush=True)

    def find(app, scen, n, engine):
        for r in runs:
            if (r["app"], r["scenario"], r["n_wgs"], r["engine"]) == \
                    (app, scen, n, engine):
                return r
        return None

    speedups = {}
    for app in args.apps:
        for scen in args.scenarios:
            for n_wgs in args.sizes:
                bat = find(app, scen, n_wgs, "batched")
                ser = find(app, scen, n_wgs, "serial")
                seed = find(app, scen, n_wgs, "seed")
                if not bat:
                    continue
                entry = {}
                if ser:
                    entry["batched_vs_serial"] = round(
                        ser["steady_s_per_iter"] / bat["steady_s_per_iter"], 2)
                if seed:
                    entry["batched_vs_seed"] = round(
                        seed["steady_s_per_iter"] / bat["steady_s_per_iter"], 2)
                    entry["serial_vs_seed"] = round(
                        seed["steady_s_per_iter"] / ser["steady_s_per_iter"], 2) \
                        if ser else None
                speedups[f"{app}/{scen}/n_wgs={n_wgs}"] = entry

    doc = {
        "bench": "protocol_engine",
        "metric_note": "speedups compare steady-state wall-clock per "
                       "simulator iteration (run_app minus one-time jit "
                       "compile); compile_s is reported separately per run",
        "backend": runs[0]["backend"] if runs else None,
        "config": {"apps": args.apps, "scenarios": args.scenarios,
                   "sizes": args.sizes, "iters": args.iters,
                   "seed_src": args.seed_src},
        "runs": runs,
        "speedups": speedups,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")
    for k, v in speedups.items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
