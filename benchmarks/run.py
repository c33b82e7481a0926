"""Benchmark aggregator: one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows per the harness contract.

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import sys
import time


def section(title):
    print(f"\n# === {title} ===", flush=True)


def main():
    quick = "--quick" in sys.argv
    t_all = time.time()

    section("paper Fig4/5/6 + scaling (work-stealing scenarios)")
    from benchmarks import paper_figs
    paper_figs.main(8 if quick else 16)

    print(f"\n[benchmarks done in {time.time()-t_all:.0f}s]")


if __name__ == "__main__":
    main()
