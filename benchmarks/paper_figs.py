"""Paper reproduction benchmarks (Fig 4, 5, 6 + the scalability claim).

Fig 4 — speedup of {scope_only, steal_only, rsp, srsp} over Baseline for
        PageRank / SSSP / MIS on DIMACS-like synthetic graphs.
Fig 5 — L2 data transactions per scenario (bandwidth proxy).
Fig 6 — sync overhead of sRSP relative to RSP.
Scaling — sRSP vs RSP remote-op cost as the CU count grows (8..64): the
        paper's core claim is that RSP's flush-all cost scales with CUs
        while sRSP's selective flush does not.
"""
from __future__ import annotations

import json
import math
import os
import time

from repro.data.graphs import collab_like, road_like, router_like
from repro.workloads.worksteal import WSConfig, run_app, reference_solution

SCENARIOS = ["baseline", "scope_only", "steal_only", "rsp", "srsp"]

# (app, graph builder, iters) — graph scales chosen for the CPU simulator;
# character matches the paper's inputs (EXPERIMENTS.md §Repro notes)
APPS = [
    ("pagerank", lambda: collab_like(n=2048, m=6, seed=0), 3),
    ("sssp", lambda: road_like(n=2025, seed=2), 8),
    ("mis", lambda: router_like(n=2048, seed=1), 6),
]


def run_all(n_wgs: int = 16, out_dir: str = "artifacts/paper"):
    os.makedirs(out_dir, exist_ok=True)
    results = {}
    for app, build, iters in APPS:
        g = build()
        n_chunks = min((g.n + 31) // 32, 256)
        ws = WSConfig(n_wgs=n_wgs, chunk_cap=32, n_chunks_max=n_chunks)
        ref = reference_solution(app, g, max_iters=iters)
        for scen in SCENARIOS:
            t0 = time.perf_counter()
            r = run_app(app, g, scen, ws, max_iters=iters)
            ok = r.proc_errors == 0
            if app == "pagerank":
                import numpy as np
                ok = ok and np.allclose(r.solution, ref, rtol=1e-4)
            results[(app, scen)] = {
                "makespan": r.makespan, "ok": bool(ok),
                "wall_s": round(time.perf_counter() - t0, 1),
                **{k: r.counters[k] for k in
                   ("l2_accesses", "wb_blocks", "inv_full", "steals",
                    "remote_syncs", "promotions", "probes")}}
            print(f"  {app:9s} {scen:11s} makespan={r.makespan:12.0f} "
                  f"l2={r.counters['l2_accesses']:9.0f} ok={ok}", flush=True)
    json.dump({f"{a}|{s}": v for (a, s), v in results.items()},
              open(os.path.join(out_dir, f"figs_{n_wgs}wg.json"), "w"),
              indent=1)
    return results


def fig4_rows(results):
    rows = []
    geo = {s: 1.0 for s in SCENARIOS}
    n = 0
    for app, _, _ in APPS:
        base = results[(app, "baseline")]["makespan"]
        n += 1
        for s in SCENARIOS:
            sp = base / results[(app, s)]["makespan"]
            geo[s] *= sp
            rows.append((app, s, sp))
    for s in SCENARIOS:
        rows.append(("geomean", s, geo[s] ** (1.0 / n)))
    return rows


def fig5_rows(results):
    rows = []
    for app, _, _ in APPS:
        base = max(results[(app, "baseline")]["l2_accesses"], 1.0)
        for s in SCENARIOS:
            rows.append((app, s, results[(app, s)]["l2_accesses"] / base))
    return rows


def fig6_rows(results):
    """Sync overhead of sRSP relative to RSP: extra cycles spent on remote
    sync machinery (makespan - scope_only work floor)."""
    rows = []
    for app, _, _ in APPS:
        floor = results[(app, "srsp")]["makespan"]
        over_rsp = results[(app, "rsp")]["makespan"]
        rows.append((app, "srsp_vs_rsp",
                     results[(app, "srsp")]["makespan"] / over_rsp))
        del floor
    return rows


def scaling_sweep(out_dir: str = "artifacts/paper"):
    """Remote-op cost vs CU count — the scalability claim (§1, §7)."""
    rows = []
    g = collab_like(n=1024, m=5, seed=0)
    for n_wgs in (8, 16, 32, 64):
        ws = WSConfig(n_wgs=n_wgs, chunk_cap=32, n_chunks_max=64)
        out = {}
        for scen in ("rsp", "srsp"):
            r = run_app("pagerank", g, scen, ws, max_iters=2)
            rem = max(r.counters["remote_syncs"], 1.0)
            out[scen] = {
                "makespan": r.makespan,
                "inv_per_remote": r.counters["inv_full"] / rem,
                "wb_per_remote": r.counters["wb_blocks"] / rem,
                "l2": r.counters["l2_accesses"],
            }
        rows.append({"n_wgs": n_wgs, **{f"{s}_{k}": v
                                        for s, d in out.items()
                                        for k, v in d.items()}})
        print(f"  scaling n_wgs={n_wgs:3d} "
              f"rsp_inv/remote={out['rsp']['inv_per_remote']:6.1f} "
              f"srsp_inv/remote={out['srsp']['inv_per_remote']:6.2f}",
              flush=True)
    json.dump(rows, open(os.path.join(out_dir, "scaling.json"), "w"),
              indent=1)
    return rows


def main(n_wgs: int = 16):
    from repro.runtime import compile_cache
    compile_cache.enable()
    print(f"[paper figs] scenarios x apps at {n_wgs} work-groups")
    results = run_all(n_wgs=n_wgs)
    print("\nFig4 speedup over Baseline:")
    for app, s, sp in fig4_rows(results):
        print(f"  {app:9s} {s:11s} {sp:5.2f}x")
    print("\nFig5 relative L2 accesses:")
    for app, s, rel in fig5_rows(results):
        print(f"  {app:9s} {s:11s} {rel:6.3f}")
    print("\nScaling sweep (RSP vs sRSP invalidations per remote op):")
    scaling_sweep()


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("n_wgs", nargs="?", type=int, default=16,
                    help="work-groups per scenario run (default 16)")
    main(ap.parse_args().n_wgs)
