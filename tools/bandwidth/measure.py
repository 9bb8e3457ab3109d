#!/usr/bin/env python
"""Collective bandwidth measurement (ref `tools/bandwidth/measure.py`,
SURVEY.md §2.8): times allreduce (psum) across the device mesh over a
sweep of tensor sizes and reports achieved GB/s — ICI on a real slice,
host rings on the virtual CPU mesh.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bandwidth/measure.py --sizes 1,8,64 --devices 8
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def measure(sizes_mb, n_devices=None, runs=5):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import incubator_mxnet_tpu.parallel as par

    n = n_devices or len(jax.devices())
    mesh = par.create_mesh(data=n)

    results = []
    for mb in sizes_mb:
        n_elem = int(mb * 1024 * 1024 / 4)
        n_elem = max(n, n_elem - n_elem % n)
        x = jnp.ones((n_elem,), jnp.float32)

        fn = jax.jit(shard_map(lambda xs: jax.lax.psum(xs, "data"),
                               mesh=mesh, in_specs=P("data"),
                               out_specs=P("data")))
        r = fn(x)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(runs):
            r = fn(x)
        float(jnp.sum(r))  # value fetch: real sync
        dt = (time.perf_counter() - t0) / runs
        # per-device shard is x.size/n; ring allreduce moves 2*(n-1)/n
        # of THAT buffer per device
        gbytes = (x.size / n) * 4 * 2 * (n - 1) / n / 1e9
        # four significant digits: a slow host must not round a rate to 0
        results.append({"size_mb": mb, "time_ms": float(f"{dt * 1e3:.4g}"),
                        "GBps": float(f"{gbytes / dt:.4g}")})
        print(results[-1])
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description="allreduce bandwidth sweep")
    p.add_argument("--sizes", type=str, default="1,4,16,64",
                   help="comma-separated MB sizes")
    p.add_argument("--devices", type=int, default=None)
    p.add_argument("--runs", type=int, default=5)
    args = p.parse_args(argv)
    measure([float(s) for s in args.sizes.split(",")], args.devices, args.runs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
