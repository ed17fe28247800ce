"""Ex09: dense linear algebra through the runtime, both granularities.

The JDF tutorials (Ex01-Ex08) show the surface language; this example
shows the Python builder API on the framework's headline workload —
Cholesky factorization — in its two dataflow shapes:

  * tiled   (build_potrf):        the DPLASMA dpotrf_L DAG over nb x nb
                                  tiles on a PxQ block-cyclic grid — the
                                  distributed form (reference:
                                  dplasma/lib/dpotrf_L.jdf role)
  * panels  (build_potrf_panels): full-height N x nb panel tasks, each
                                  trailing update ONE MXU matmul — the
                                  TPU-shaped single-chip form bench.py
                                  measures

Run:  python examples/Ex09_PanelCholesky.py [N] [nb]
The kernels run on the device JAX gives it (JAX_PLATFORMS=cpu for the
CPU backend).
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import parsec_tpu as pt  # noqa: E402
from parsec_tpu.algos import build_potrf, build_potrf_panels  # noqa: E402
from parsec_tpu.data import TwoDimBlockCyclic  # noqa: E402
from parsec_tpu.device import TpuDevice  # noqa: E402


def main():
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    nb = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    rng = np.random.default_rng(0)
    M = rng.standard_normal((N, N), dtype=np.float32)
    spd = M @ M.T + N * np.eye(N, dtype=np.float32)
    ref = np.linalg.cholesky(spd)

    with pt.Context(nb_workers=4) as ctx:
        dev = TpuDevice(ctx)

        # ---- tiled (distributed form; here single-rank) ----
        A = TwoDimBlockCyclic(N, N, nb, nb, dtype=np.float32)
        A.from_dense(spd)
        A.register(ctx, "A")
        tp = build_potrf(ctx, A, dev=dev)
        tp.run()
        tp.wait()
        dev.flush()
        err = np.abs(np.tril(A.to_dense()) - ref).max()
        print(f"tiled  potrf: N={N} nb={nb} max|err|={err:.2e}")

        # ---- panel-granular (single-chip headline form) ----
        P = TwoDimBlockCyclic(N, N, N, nb, dtype=np.float32)
        for j in range(P.nt):
            P.tile(0, j)[...] = spd[:, j * nb:(j + 1) * nb]
        P.register(ctx, "P")
        tp2 = build_potrf_panels(ctx, P, dev=dev, name="P")
        tp2.run()
        tp2.wait()
        dev.flush()
        out = np.zeros((N, N), np.float32)
        for j in range(P.nt):
            out[:, j * nb:(j + 1) * nb] = P.tile(0, j)
        err2 = np.abs(np.tril(out) - ref).max()
        print(f"panels potrf: N={N} nb={nb} max|err|={err2:.2e}")
        s = dev.stats
        print(f"device: {dev.device.platform} tasks={s['tasks']} "
              f"batches={s['batches']} fused_flows={s['fused_flows']}")
        dev.stop()
    assert err < 5e-3 and err2 < 5e-3


if __name__ == "__main__":
    main()
