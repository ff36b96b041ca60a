#!/usr/bin/env python
"""Measure collective-communication bandwidth over the device mesh.

Reference: ``tools/bandwidth/measure.py`` — times kvstore push/pull of
ResNet-sized gradients to estimate aggregation bandwidth. The TPU twin
times the collectives XLA actually emits (psum / all_gather /
reduce_scatter under shard_map over a Mesh) — on real hardware these ride
the ICI links; on the CPU rig they exercise the same code path for
plumbing checks.

Usage:
    python tools/bandwidth.py --size-mb 64 --iters 10
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bandwidth.py
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64.0,
                    help="payload per device, megabytes")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--collectives", type=str,
                    default="psum,all_gather,reduce_scatter")
    args = ap.parse_args(argv)

    import jax
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(devs, ("x",))

    def smap(fn, in_specs, out_specs):
        # the replication checker can't infer psum outputs; disable it
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
    elems = int(args.size_mb * 1e6 / 4)
    elems -= elems % max(n, 1)
    x = jnp.ones((elems,), jnp.float32)

    def timed(fn, arr):
        jax.block_until_ready(fn(arr))              # compile + warm up
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(arr)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters

    results = {}
    wanted = args.collectives.split(",")

    if "psum" in wanted:
        f = jax.jit(smap(lambda v: jax.lax.psum(v, "x"), P("x"), P()))
        dt = timed(f, x)
        # ring all-reduce moves ~2*(n-1)/n of the buffer per device
        gb = x.nbytes * 2 * (n - 1) / max(n, 1) / 1e9
        results["psum"] = (dt, gb / dt)
    if "all_gather" in wanted:
        f = jax.jit(smap(lambda v: jax.lax.all_gather(v, "x", tiled=True),
                         P("x"), P()))
        dt = timed(f, x)
        gb = x.nbytes * (n - 1) / max(n, 1) / 1e9
        results["all_gather"] = (dt, gb / dt)
    if "reduce_scatter" in wanted:
        f = jax.jit(smap(lambda v: jax.lax.psum_scatter(v, "x",
                                                        tiled=True),
                         P("x"), P("x")))
        dt = timed(f, x)
        gb = x.nbytes * (n - 1) / max(n, 1) / 1e9
        results["reduce_scatter"] = (dt, gb / dt)

    print("devices: %d (%s), payload %.1f MB"
          % (n, devs[0].platform, x.nbytes / 1e6))
    for name, (dt, bw) in results.items():
        print("%-15s %8.3f ms   %8.2f GB/s algorithmic" %
              (name, dt * 1e3, bw))
    return 0


if __name__ == "__main__":
    sys.exit(main())
