#!/usr/bin/env python
"""Per-kernel cost probe for the dense-LA tile kernels (potrf.py) on
whatever chip JAX sees — answers WHERE the spotrf wall time goes before
any optimization is attempted (VERDICT r3 weak #1 follow-through: make
perf work data-driven).

Times, per tile shape (NB x NB) and batch width B:
  chol      jnp.linalg.cholesky           (POTRF diagonal, B=1)
  trsm      vmapped solve_triangular      (TRSM panel wave)
  trsm_inv  tri inverse once + vmapped GEMM against it (the MXU-friendly
            TRSM replacement: solve_triangular(L, I) -> batched matmul)
  syrk      vmapped A@A^T subtract        (SYRK wave)
  gemm      vmapped A@B^T subtract        (GEMM wave, the FLOPs bulk)
  launch    empty-ish kernel (x+1 on 8 floats) — per-call dispatch floor

Emits one JSON line per measurement:
  {"kernel": k, "nb": NB, "batch": B, "ms": t, "gflops": g, "chip": kind}
"""
import json
import sys
import time

import numpy as np


def _force(out):
    """Block until the call's results are on the device."""
    import jax
    jax.block_until_ready(out)


def _time(f, *args, reps=5):
    """Median wall of reps calls, forcing the result each time."""
    _force(f(*args))  # compile + settle
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _force(f(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def main():
    import jax
    import jax.numpy as jnp
    from parsec_tpu.algos.potrf import k_gemm, k_potrf, k_syrk, k_trsm

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "cpu")
    nbs = [512]
    if "--nb" in sys.argv:
        nbs = [int(sys.argv[sys.argv.index("--nb") + 1])]
    batches = [8, 32]
    if "--batch" in sys.argv:
        batches = [int(sys.argv[sys.argv.index("--batch") + 1])]

    def emit(kernel, nb, batch, dt, flops):
        print(json.dumps({"kernel": kernel, "nb": nb, "batch": batch,
                          "ms": round(dt * 1e3, 3),
                          "gflops": round(flops / dt / 1e9, 1),
                          "chip": kind}), flush=True)

    # dispatch floor: what does ANY call cost end to end?
    tiny = jnp.ones((8,), jnp.float32)
    f_launch = jax.jit(lambda x: x + 1.0)
    emit("launch", 0, 1, _time(f_launch, tiny), 0.0)

    for nb in nbs:
        rng = np.random.default_rng(0)
        spd = rng.standard_normal((nb, nb), dtype=np.float32)
        spd = spd @ spd.T + nb * np.eye(nb, dtype=np.float32)
        t_d = jax.device_put(spd, dev)
        l_d = jax.device_put(np.linalg.cholesky(spd), dev)

        emit("chol", nb, 1, _time(jax.jit(k_potrf), t_d), nb ** 3 / 3)
        emit("trsm", nb, 1, _time(jax.jit(k_trsm), l_d, t_d), nb ** 3)

        for b in batches:
            c_b = jax.device_put(
                rng.standard_normal((b, nb, nb), dtype=np.float32), dev)
            a_b = jax.device_put(
                rng.standard_normal((b, nb, nb), dtype=np.float32), dev)
            t_b = jax.device_put(
                np.broadcast_to(spd, (b, nb, nb)).copy(), dev)

            emit("trsm", nb, b,
                 _time(jax.jit(jax.vmap(k_trsm, in_axes=(None, 0))),
                       l_d, c_b), b * nb ** 3)

            # the MXU-friendly TRSM: invert the (tiny) triangle once,
            # then the whole wave is one batched GEMM
            def trsm_inv(l, cs):
                linv = jax.scipy.linalg.solve_triangular(
                    l, jnp.eye(l.shape[0], dtype=l.dtype), lower=True)
                return jax.lax.dot_general(
                    cs, linv, (((2,), (1,)), ((), ())),
                    preferred_element_type=cs.dtype)
            emit("trsm_inv", nb, b, _time(jax.jit(trsm_inv), l_d, c_b),
                 b * nb ** 3)

            emit("syrk", nb, b,
                 _time(jax.jit(jax.vmap(k_syrk)), a_b, t_b),
                 b * nb ** 3)
            emit("gemm", nb, b,
                 _time(jax.jit(jax.vmap(k_gemm)), a_b, c_b, t_b),
                 2 * b * nb ** 3)


if __name__ == "__main__":
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
