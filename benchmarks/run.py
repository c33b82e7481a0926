"""Benchmark aggregator: one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows per the harness contract.

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def section(title):
    print(f"\n# === {title} ===", flush=True)


def main():
    quick = "--quick" in sys.argv
    t_all = time.time()

    section("kernel micro-benchmarks (name,us_per_call,derived)")
    from benchmarks import kernel_bench
    kernel_bench.main()

    section("paper Fig4/5/6 + scaling (work-stealing scenarios)")
    from benchmarks import paper_figs
    paper_figs.main(8 if quick else 16)

    section("sRSP cross-pod selective delta sync (framework layer)")
    # a simulated 8-device pod axis on host CPUs: the child never needs
    # the accelerator this process may already hold
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu", PYTHONPATH="src")
    subprocess.run([sys.executable, "-m", "benchmarks.delta_sync_bench"],
                   env=env, check=True)

    section("roofline table (from dry-run artifacts)")
    if os.path.isdir("artifacts/dryrun"):
        from benchmarks import roofline
        rows = roofline.load()
        if rows:
            print(roofline.table(rows))
    section("analytic roofline (primary §Roofline artifact)")
    from benchmarks.analytic_roofline import main as arl
    arl()

    print(f"\n[benchmarks done in {time.time()-t_all:.0f}s]")


if __name__ == "__main__":
    main()
