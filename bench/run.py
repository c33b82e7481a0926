"""Benchmark of the simulator's sweep path on one TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of `BENCHMARK.json` names a configuration (`bench/configs/`) and a
traffic file (`bench/traffic/`); its workload module has a driver under
`bench/drivers/`.  The run refuses any `REPRO_*` variable, a device
other than a TPU, fewer chips than the cell asks for, and any kernel
mode but "pallas".  Set-up compiles the cell's programs (JAX's
persistent cache sits in `<checkout>/.jax_cache` unless
JAX_COMPILATION_CACHE_DIR says otherwise) and makes one warm-up call.
The window then runs whole calls back to back until `--seconds` have
passed.  A call is two steps, each ended by `block_until_ready`: make
the input state from seeds derived from `--seed` and the call index,
then run the engine on it.

After the window every call's results are checked (every request served,
no stale read), and one call drawn from the seed is compared, replica
by replica and value by value, with the plain reference under
`bench/reference/`.  With `--trace 1` the first calls of the window are
profiled and the per-layer metrics are read from the trace by the
readers in `bench/metrics/`.  The last line of standard output is the
result as JSON; the numbers compared, with their limits, are its last
key and the last lines of standard error.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import spec  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_SECONDS = 2.0      # profile whole calls until this much has passed
PLATFORM, KERNEL_MODE = "tpu", "pallas"


class Refused(Exception):
    """The run cannot measure what the cell asks for; no result."""


def call_seeds(seed: int, k: int, replicas: int) -> np.ndarray:
    """uint32 seeds of the replicas of call k (k=0 is the warm-up)."""
    ss = np.random.SeedSequence([seed & (2 ** 64 - 1), k])
    return ss.generate_state(replicas, dtype=np.uint32)


def preflight(chips: int):
    """Refuse what cannot be measured; -> the devices to run on."""
    forced = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if forced:
        raise Refused(f"refusing to run with {forced} set")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused(f"no program under {ROOT}/src/repro")
    from repro.runtime import compile_cache
    compile_cache.enable()
    import jax

    from repro.kernels import common
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise Refused(f"need a {PLATFORM} device, found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"cell needs {chips} chips, found {len(devs)}")
    if common.kernel_mode() != KERNEL_MODE:
        raise Refused(f"kernel_mode is {common.kernel_mode()!r}, "
                      f"need {KERNEL_MODE!r}")
    return devs[:chips]


def load_driver(cell: spec.Cell, proto=None):
    mod = importlib.import_module("bench.drivers." + cell.config["workload"])
    return mod.Driver(cell.config, cell.traffic, proto)


def _one_call(drv, seeds, spans=None):
    """Make the inputs, run the engine; -> the fetched result leaves."""
    import jax
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    with TraceAnnotation("bench.init"):
        inp = jax.block_until_ready(drv.inputs(seeds))
    t1 = time.perf_counter()
    with TraceAnnotation("bench.call"):
        out = jax.block_until_ready(drv.call(inp))
    t2 = time.perf_counter()
    if spans is not None:
        spans.append((t0, t1, t2))
    return drv.fetch(out)


def window(drv, seed: int, seconds: float, trace: bool) -> dict:
    """Whole calls back to back until `seconds` have passed."""
    import jax
    spans, fetched, seeds = [], [], []
    tracing = trace
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # device ops and host spans only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    t0 = time.perf_counter()
    k = 1
    while True:
        s = call_seeds(seed, k, drv.replicas)
        fetched.append(_one_call(drv, s, spans))
        seeds.append(s)
        k += 1
        now = time.perf_counter()
        if tracing and now - t0 >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing = False
        if now - t0 >= seconds:
            break
    t1 = time.perf_counter()
    if tracing:
        jax.profiler.stop_trace()
    return dict(t0=t0, t1=t1, spans=spans, fetched=fetched, seeds=seeds)


def compare(prog: dict, ref: dict) -> tuple:
    """(values that differ, values compared) between one replica's
    program statistics and the reference's, exactly."""
    diff = total = 0
    for k, r in ref.items():
        p = np.asarray(prog[k])
        r = np.asarray(r)
        total += r.size
        diff += r.size if p.shape != r.shape else int(np.sum(p != r))
    return diff, total


def judge(drv, win: dict, seed: int) -> dict:
    """Every call's accounting, and one call drawn from the seed against
    the reference.  -> the numbers compared, each with its limit."""
    host = [{k: np.asarray(v) for k, v in f.items()} for f in win["fetched"]]
    unserved = stale = 0
    for h in host:
        u, s = drv.served(h)
        unserved += u
        stale += s
    pick = int(np.random.default_rng([seed & (2 ** 64 - 1), 7]).integers(
        len(host)))
    diff = total = 0
    for r, ref in enumerate(drv.reference(win["seeds"][pick])):
        d, t = compare(drv.replica(host[pick], r), ref)
        diff += d
        total += t
    return {"unserved": {"value": unserved, "limit": 0},
            "stale_reads": {"value": stale, "limit": 0},
            "ref_mismatches": {"value": diff, "limit": 0,
                               "of": total, "call": pick}}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             devices=None, drv=None, t_start: float = None) -> dict:
    """Set up, warm up, measure, judge.  -> the result line as a dict.
    `devices=None` skips nothing but the device report (tests on CPU)."""
    import jax
    t_start = T_START if t_start is None else t_start
    drv = drv or load_driver(cell)
    t0 = time.perf_counter()
    programs = drv.compile()
    compile_s = time.perf_counter() - t0
    _one_call(drv, call_seeds(seed, 0, drv.replicas))
    setup_s = time.perf_counter() - t_start

    win = window(drv, seed, seconds, trace)
    dev = (devices or jax.devices())[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices or jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    checks = judge(drv, win, seed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    wall = win["t1"] - win["t0"]
    n_calls = len(win["spans"])
    attempted = n_calls * drv.events_per_call
    result = {"correct": correct, "attempted": attempted,
              "failed": checks["unserved"]["value"]}
    if trace:
        from bench import metrics
        ctx = metrics.Context(
            spans=win["spans"], compile_s=compile_s,
            trace_dir=TRACE_DIR,
            hlo_texts=[p.as_text() for p in programs.values()])
        result["metrics"] = metrics.read_all(cell.per_layer, ctx)
        print(f"trace: {ctx.unmatched_share} of device busy time in ops "
              f"not found in the programs' HLO (at most "
              f"{metrics.MAX_UNMATCHED})", file=sys.stderr)
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        result["breakdown"] = ctx.breakdown()
    else:
        measured = {"sim_events_per_s": attempted / wall, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        devices = preflight(cell.chips)
    except (Refused, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
