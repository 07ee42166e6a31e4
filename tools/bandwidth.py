#!/usr/bin/env python
"""Measure collective bandwidth over the device mesh
(ref: tools/bandwidth/measure.py — kvstore all-reduce bandwidth tool,
re-pointed at ICI collectives)."""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64.0)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force N virtual CPU devices")
    args = ap.parse_args()

    import jax

    if args.cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    devs = jax.devices()
    n = len(devs)
    elems = int(args.size_mb * 1e6 / 4)
    elems -= elems % max(n, 1)
    import numpy as np

    mesh = Mesh(np.array(devs), ("dp",))
    x = jnp.ones((elems,), jnp.float32)

    @jax.jit
    def allreduce(x):
        f = jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                      in_specs=PartitionSpec("dp"),
                      out_specs=PartitionSpec())
        return f(x)

    allreduce(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = allreduce(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / args.iters
    # ring all-reduce moves 2*(n-1)/n of the data per device
    algbw = args.size_mb / 1e3 / dt
    busbw = algbw * 2 * (n - 1) / max(n, 1)
    print(f"devices={n} size={args.size_mb}MB time={dt*1e3:.2f}ms "
          f"algbw={algbw:.2f}GB/s busbw={busbw:.2f}GB/s")


if __name__ == "__main__":
    main()
