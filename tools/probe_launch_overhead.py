#!/usr/bin/env python
"""Per-executable-call overhead on the real chip.

The spotrf wall tracks the number of device dispatches, not FLOPs — this
probe separates the two candidate explanations:

  * serialized per-call overhead (each execute waits on the last):
    dependent-chain time/call ~= independent-burst time/call
  * pipelined enqueue (client streams executions, device runs them
    back-to-back): independent-burst time/call << dependent-chain
    time/call

Emits one JSON line:
  {"metric": "launch_overhead", "dep_us_per_call": ..,
   "indep_us_per_call": .., "tiny_flops_ms": .., "chip_kind": ..}

Method: jit(x -> x + 1) on a 128x128 f32.  Dependent chain feeds each
call's output to the next (no host sync between calls); independent
burst reuses the same input 100 times; one final block_until_ready
closes each timing.  A third number times a single big 4096^3 matmul
for scale.  Everything is warmed before timing.
"""
import json
import sys
import time


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    n = 100

    @jax.jit
    def bump(x):
        return x + 1.0

    x0 = jax.device_put(jnp.zeros((128, 128), jnp.float32), dev)
    bump(x0).block_until_ready()  # warm/compile

    # dependent chain: each call consumes the previous result
    x = x0
    t0 = time.perf_counter()
    for _ in range(n):
        x = bump(x)
    x.block_until_ready()
    dep_us = (time.perf_counter() - t0) / n * 1e6

    # independent burst: same input every time (client may pipeline)
    t0 = time.perf_counter()
    ys = [bump(x0) for _ in range(n)]
    ys[-1].block_until_ready()
    for y in ys:
        y.block_until_ready()
    indep_us = (time.perf_counter() - t0) / n * 1e6

    # scale bar: one large matmul (MXU-bound)
    a = jax.device_put(jnp.ones((4096, 4096), jnp.float32), dev)
    mm = jax.jit(lambda p: p @ p)
    mm(a).block_until_ready()
    t0 = time.perf_counter()
    mm(a).block_until_ready()
    big_ms = (time.perf_counter() - t0) * 1e3

    print(json.dumps({
        "metric": "launch_overhead",
        "dep_us_per_call": round(dep_us, 1),
        "indep_us_per_call": round(indep_us, 1),
        "big_matmul_4096_ms": round(big_ms, 2),
        "chip_kind": getattr(dev, "device_kind", "?"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
