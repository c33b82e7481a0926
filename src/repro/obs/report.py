"""Trace report CLI (DESIGN.md §11).

    python -m repro.obs.report TRACE.json          # report an exported trace
    python -m repro.obs.report --demo              # run + trace a demo cell

The demo runs the pinned churned worksteal cell (die-holding-lock crash,
lease-expiry recovery — the richest event mix: local/remote sync ops,
probes, a CHURN instant and a RECOVER drain) with tracing force-enabled
in-process via `trace.with_trace`, exports Perfetto-loadable JSON, and
prints the text report.  `make trace` drives exactly this.
"""
from __future__ import annotations

import argparse
import json
import sys


def _run_demo(args):
    import jax
    import numpy as np

    from repro import workloads
    from repro.core import protocol as P
    from repro.obs import export, spans, trace as T
    from repro.workloads import faults, harness

    victim, at, evt = 0, 5.0, 400.0   # tests/test_churn.py's pinned geometry
    mod = workloads.get(args.workload)
    proto = None
    events = []
    kw = {}
    if args.workload == "worksteal":
        proto = faults.crash_holding_lock(
            P.get_protocol(args.scenario), victim, at)
        events = [(evt, victim, "crash")]
        kw["n_chunks_max"] = 12
    bench = mod.build(args.scenario, args.n_agents, seed=3, proto=proto,
                      **kw)
    eb = harness.make_elastic(bench, events=events)
    state = T.with_trace(eb.state, args.cap)
    with spans.span(
            f"demo:{args.workload}/{args.scenario}/n={args.n_agents}"):
        fin = harness.run_batched_elastic(eb.wl, state, *eb.ops)
        jax.block_until_ready(fin.s.store.counters.cycles)
    res = eb.check(fin)
    label = (f"{args.workload}/{args.scenario}/n={args.n_agents}/"
             f"batched_elastic"
             + ("+crash" if events else ""))
    doc = export.write_trace(args.out, fin.s.store, label=label)
    rec = float(np.sum(np.asarray(fin.s.store.counters.recoveries)))
    print(export.text_report(doc))
    print(f"check_ok={res['ok']} recovered={rec:.0f}")
    print(f"wrote {args.out}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("trace", nargs="?", help="exported trace JSON to report")
    ap.add_argument("--demo", action="store_true",
                    help="run + trace the demo cell, then report it")
    ap.add_argument("--workload", default="worksteal")
    ap.add_argument("--scenario", default="srsp")
    ap.add_argument("-n", "--n-agents", type=int, default=4)
    ap.add_argument("--cap", type=int, default=None,
                    help="ring capacity for --demo (default REPRO_TRACE_CAP)")
    ap.add_argument("--out", default="TRACE_demo.json",
                    help="output JSON for --demo")
    args = ap.parse_args(argv)
    if args.demo:
        return _run_demo(args)
    if not args.trace:
        ap.error("need a trace JSON path or --demo")
    from repro.obs import export
    with open(args.trace) as f:
        doc = json.load(f)
    print(export.text_report(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
