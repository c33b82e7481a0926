"""Paper reproduction driver: work-stealing graph workloads under the five
evaluation scenarios (paper §5) — Baseline / Scope-only / Steal-only /
RSP / sRSP — on DIMACS-like synthetic graphs.

  PYTHONPATH=src python examples/worksteal_graphs.py [--wgs 16] [--app pagerank]
"""
import argparse

import numpy as np

from repro.data.graphs import GRAPHS, collab_like, road_like, router_like
from repro.workloads.worksteal import (ENGINES, WSConfig, reference_solution,
                                       run_app)

SCENARIOS = ["baseline", "scope_only", "steal_only", "rsp", "srsp"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="pagerank",
                    choices=["pagerank", "sssp", "mis"])
    ap.add_argument("--wgs", type=int, default=16)
    ap.add_argument("--nodes", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--engine", default="batched",
                    choices=sorted(ENGINES),
                    help="vectorized scheduler (default), the serial "
                         "reference engine, or the fused megakernel trip "
                         "(identical counters, see DESIGN.md §4, §12)")
    args = ap.parse_args()

    g = {"pagerank": collab_like, "sssp": road_like,
         "mis": router_like}[args.app](args.nodes)
    print(f"graph={g.name} nnz={g.nnz}  app={args.app}  wgs={args.wgs}\n")
    ws = WSConfig(n_wgs=args.wgs, chunk_cap=32,
                  n_chunks_max=min((g.n + 31) // 32, 256))
    ref = reference_solution(args.app, g, max_iters=args.iters)
    base = None
    print(f"{'scenario':12s} {'makespan':>12s} {'speedup':>8s} {'L2 acc':>9s} "
          f"{'steals':>7s} {'inv':>6s} {'sol ok':>7s}")
    for scen in SCENARIOS:
        r = run_app(args.app, g, scen, ws, max_iters=args.iters,
                    engine=args.engine)
        ok = r.proc_errors == 0
        if args.app == "pagerank":
            ok = ok and np.allclose(r.solution, ref, rtol=1e-4)
        else:
            ok = ok and np.array_equal(r.solution, ref)
        if base is None:
            base = r.makespan
        print(f"{scen:12s} {r.makespan:12.0f} {base/r.makespan:7.2f}x "
              f"{r.counters['l2_accesses']:9.0f} {r.counters['steals']:7.0f} "
              f"{r.counters['inv_full']:6.0f} {str(ok):>7s}")


if __name__ == "__main__":
    main()
