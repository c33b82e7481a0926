"""Kernel micro-benchmarks: wall time of the jnp reference path on CPU (the
Pallas path is TPU-targeted; interpret mode timing is not meaningful), plus
derived bytes/flops so the table carries roofline context."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=10):
    fn(*args)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def rows():
    rng = np.random.default_rng(0)
    out = []

    from repro.kernels.selective_flush.ref import selective_flush_ref
    bank = jnp.asarray(rng.normal(size=(4096, 512)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 4096, 128).astype(np.int32))
    us = _time(jax.jit(selective_flush_ref), bank, idx)
    out.append(("selective_flush_4096x512_d128", us,
                f"{128*512*4/us/1e3:.2f}GB/s"))

    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    x = jnp.asarray(rng.normal(size=(4096, 4096)).astype(np.float32))
    w = jnp.ones((4096,), jnp.float32)
    us = _time(jax.jit(rmsnorm_ref), x, w)
    out.append(("rmsnorm_4096x4096", us, f"{2*x.size*4/us/1e3:.2f}GB/s"))

    from repro.models.layers import blockwise_attention
    q = jnp.asarray(rng.normal(size=(1, 8, 1024, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 1024, 64)).astype(np.float32))
    f = jax.jit(lambda a, b, c: blockwise_attention(a, b, c, block_k=256))
    us = _time(f, q, k, q[:, :2] * 0 + k)
    flops = 4 * 1 * 8 * 1024 * 1024 * 64 / 2
    out.append(("blockwise_attn_1x8x1024x64", us, f"{flops/us/1e6:.2f}GFLOP/s"))

    from repro.kernels.flash_decode.ref import decode_attention_ref
    qd = jnp.asarray(rng.normal(size=(4, 8, 64)).astype(np.float32))
    kd = jnp.asarray(rng.normal(size=(4, 2, 8192, 64)).astype(np.float32))
    kvl = jnp.full((4,), 8192, jnp.int32)
    us = _time(jax.jit(decode_attention_ref), qd, kd, kd, kvl)
    out.append(("decode_attn_4x8_kv8192", us,
                f"{2*kd.size*4/us/1e3:.2f}GB/s"))

    from repro.kernels.topk_router.ref import topk_router_ref
    lg = jnp.asarray(rng.normal(size=(8192, 256)).astype(np.float32))
    us = _time(jax.jit(lambda l: topk_router_ref(l, 8)), lg)
    out.append(("topk_router_8192x256_k8", us, ""))

    # fused-turn megakernel surfaces (DESIGN.md §12): the trip plan and the
    # packed-plane commit, jnp reference path, at the sweep's agent counts.
    # Both metadata layouts ride one process — plane_commit tells packed
    # (uint32) and boolean (REPRO_NO_PACK=1) planes apart by dtype.
    from repro.core import bitmask
    from repro.kernels.fused_turn.ref import plane_commit_ref, trip_plan_ref
    for n_wgs in (64, 256, 1024):
        clocks = jnp.asarray(rng.integers(0, 64, n_wgs).astype(np.float32))
        can_l = jnp.asarray(rng.random(n_wgs) < 0.6)
        can_r = jnp.asarray(rng.random(n_wgs) < 0.4)
        bound = jnp.ones((n_wgs,), jnp.float32)
        raddr = jnp.asarray(rng.integers(0, 64, n_wgs).astype(np.int32))
        us = _time(jax.jit(lambda c, l, r, bd, ra: trip_plan_ref(
            c, l, r, bd, ra, None)), clocks, can_l, can_r, bound, raddr)
        out.append((f"fused_trip_plan_n{n_wgs}", us,
                    f"{n_wgs*n_wgs/us:.0f}Mpair/s"))

        nb, W = 64, 128
        L = bitmask.n_lanes(W)
        wv = jnp.asarray(rng.integers(0, 2**32, (n_wgs, nb, L),
                                      dtype=np.uint64).astype(np.uint32))
        wd = jnp.zeros_like(wv)
        b = jnp.asarray(rng.integers(0, nb, n_wgs).astype(np.int32))
        o = jnp.asarray(rng.integers(0, W, n_wgs).astype(np.int32))
        sv = jnp.ones((n_wgs,), bool)
        us = _time(jax.jit(plane_commit_ref), wv, wd, b, o, sv, sv)
        out.append((f"plane_commit_packed_n{n_wgs}", us,
                    f"{n_wgs/us:.2f}Mlane/s"))
        wvb = bitmask.unpack(wv, W)
        us = _time(jax.jit(plane_commit_ref), wvb, jnp.zeros_like(wvb),
                   b, o, sv, sv)
        out.append((f"plane_commit_bool_n{n_wgs}", us,
                    f"{n_wgs/us:.2f}Mlane/s"))

    from repro.models.moe import moe_apply, moe_init
    from repro.models.registry import get_config
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    xm = jnp.asarray(rng.normal(size=(2048, cfg.d_model)).astype(np.float32))
    us = _time(jax.jit(lambda pp, xx: moe_apply(pp, cfg, xx)[0]), p, xm)
    out.append(("moe_dispatch_2048tok_4e", us, ""))
    return out


def main():
    from repro.kernels import common
    from repro.runtime import compile_cache
    compile_cache.enable()
    # mode is chosen once per process; an interpret-mode benchmark is a
    # user error (REPRO_KERNEL_MODE=interpret) and warns loudly
    print(f"# kernel_mode={common.note_benchmark('kernel_bench')}")
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
