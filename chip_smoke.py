"""On-chip smoke test of the simulator's sweep path (one TPU, one process).

Drives the main path through its normal entry points (`workloads.get`,
`harness.runner` / `harness.runner_many`) with the Pallas kernels
compiled for the chip, and checks every phase's modelled statistics
against the CPU reference values committed in `chip_smoke_expected.json`:

  (a) device check: the first device is a TPU, `kernel_mode()` is
      "pallas", and no REPRO_* switch is set (no CPU fallback, no
      interpret mode, no escape hatch);
  (b) kv_serving at n=64 agents, the sweep's serving-cell traffic (Zipf
      s=1.2, burstiness 4.0, gap_mean 8.0, 256 requests per agent) on 2
      vmapped replicas — 32,768 requests — on srsp/fused, srsp/batched
      and rsp/batched;
  (c) kv_directory, srsp, fused, n=64, vmapped (the remote-batching
      trip-plan kernel);
  (d) worksteal, srsp, batched, n=64 (unvmapped, host-initialised).

Each phase prints compile and steady seconds (both end in
`block_until_ready`), events, makespan, completed, check_ok and the
number of `tpu_custom_call`s in its compiled program; a phase whose
program holds none fails, as does any statistic that differs from the
reference.  The last line of a passing run is
`{"ok": true, "device": {...}}`; any failure exits nonzero without it.

  python chip_smoke.py                      # on the chip
  JAX_PLATFORMS=cpu python chip_smoke.py --write-expected
                                            # regenerate the reference
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
EXPECTED = os.path.join(ROOT, "chip_smoke_expected.json")
sys.path.insert(0, os.path.join(ROOT, "src"))

N_AGENTS = 64
REPLICAS = 2
# the sweep's serving-cell defaults (`repro.workloads.sweep --serving-*`)
SERVING = dict(requests_per_agent=256, zipf_s=1.2, gap_mean=8.0,
               burstiness=4.0, remote_frac=0.03)
# (label, workload, scenario, engine, vmapped)
PHASES = (
    ("b/kv_serving/srsp/fused", "kv_serving", "srsp", "fused", True),
    ("b/kv_serving/srsp/batched", "kv_serving", "srsp", "batched", True),
    ("b/kv_serving/rsp/batched", "kv_serving", "rsp", "batched", True),
    ("c/kv_directory/srsp/fused", "kv_directory", "srsp", "fused", True),
    ("d/worksteal/srsp/batched", "worksteal", "srsp", "batched", False),
)
_CUSTOM_CALL = re.compile(r'custom_call_target="tpu_custom_call"')


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def run_phase(workload, scenario, engine, vmapped):
    """Compile, warm up and time one phase; returns (timing, stats) where
    stats is one {counters, check} record per replica."""
    import jax
    import jax.numpy as jnp

    from repro import workloads
    from repro.traffic.samplers import TrafficConfig
    from repro.workloads import harness

    mod = workloads.get(workload)
    kw = {"traffic": TrafficConfig(**SERVING)} \
        if workload == "kv_serving" else {}
    wl = mod.build(scenario, N_AGENTS, seed=0, **kw).wl
    if vmapped:
        run = harness.runner_many(engine)
        seeds = jnp.arange(REPLICAS, dtype=jnp.int32)

        def fresh():
            states = jax.vmap(lambda s: mod.init_state(wl, s))(seeds)
            return (states,), lambda out: [
                mod.self_check(wl, jax.tree.map(lambda x: x[k], out))
                for k in range(REPLICAS)]
    else:
        run = harness.runner(engine)

        def fresh():
            b = mod.build(scenario, N_AGENTS, seed=0, **kw)
            return (b.state,) + tuple(b.ops), lambda out: [b.check(out)]

    args, _ = fresh()
    t0 = time.perf_counter()
    compiled = run.lower(wl, *args).compile()
    compile_s = time.perf_counter() - t0
    _timed(compiled, *args)                  # warm-up run (donates args)
    args, check = fresh()
    out, steady_s = _timed(compiled, *jax.block_until_ready(args))
    checks = check(out)
    stores = [jax.tree.map(lambda x: x[k], out.store)
              for k in range(REPLICAS)] if vmapped else [out.store]
    stats = [{"counters": harness.counters_dict(st), "check": ck}
             for st, ck in zip(stores, checks)]
    timing = {"compile_s": compile_s, "steady_s": steady_s,
              "tpu_custom_calls": len(_CUSTOM_CALL.findall(
                  compiled.as_text()))}
    # JSON round-trip: the committed reference went through the same one
    return timing, json.loads(json.dumps(stats))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--write-expected", action="store_true",
                    help="run the phases on the CPU in ref mode and write "
                         "the reference statistics (no device check)")
    args = ap.parse_args(argv)

    forced = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if forced:
        print(f"chip_smoke: refusing to run with {forced} set",
              file=sys.stderr)
        return 2

    from repro.runtime import compile_cache
    compile_cache.enable()
    import jax

    from repro.kernels import common

    dev = jax.devices()[0]
    want = ("cpu", "ref") if args.write_expected else ("tpu", "pallas")
    got = (dev.platform, common.kernel_mode())
    if got != want:
        print(f"chip_smoke: need platform/kernel_mode {want}, found {got}",
              file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}, kernel_mode={got[1]}", flush=True)

    results, failures = {}, []
    expected = {} if args.write_expected else json.load(open(EXPECTED))
    for label, workload, scenario, engine, vmapped in PHASES:
        timing, stats = run_phase(workload, scenario, engine, vmapped)
        results[label] = stats
        ok = all(s["check"]["ok"] for s in stats)
        match = args.write_expected or stats == expected.get(label)
        print(f"phase {label}: compile_s={timing['compile_s']:.3f} "
              f"steady_s={timing['steady_s']:.4f} "
              f"events={[s['check']['events'] for s in stats]} "
              f"makespan={[s['counters']['makespan'] for s in stats]} "
              f"completed={[s['check'].get('completed') for s in stats]} "
              f"check_ok={ok} tpu_custom_calls={timing['tpu_custom_calls']} "
              f"matches_reference={match}", flush=True)
        if not ok:
            failures.append(f"{label}: self-check failed")
        if not match:
            failures.append(f"{label}: statistics differ from "
                            f"{os.path.basename(EXPECTED)}: {stats} "
                            f"vs {expected.get(label)}")
        if not args.write_expected and timing["tpu_custom_calls"] < 1:
            failures.append(f"{label}: no tpu_custom_call in the program")

    if failures:
        print("chip_smoke FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    if args.write_expected:
        with open(EXPECTED, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {EXPECTED}")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
