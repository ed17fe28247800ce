#!/usr/bin/env python
"""Cross-PROCESS device data-plane measurement (VERDICT r3 #5).

Two separate OS processes (the real multi-host shape — no shared jax
client, so the colocated by-reference shortcut cannot apply), one
device-resident tile per size rung crossing rank 0 -> rank 1 through the
PK_DEVICE rendezvous: producing-side lazy d2h at serve time, TCP, h2d on
the consumer.  This is the fallback path whose cost decides whether a
platform-level cross-host device transfer is worth building (reference
seam: transport-native payload movement end to end,
parsec/parsec_comm_engine.h:139-160; SURVEY §7 hard-part 2).

Emits one JSON line per tile size:
  {"tile_mb": M, "xfer_ms": t, "gbps": g, "d2h_bytes": ..., "h2d_bytes": ...}

Each rank is its own process, and only one process may hold a chip, so
the ranks run on JAX's CPU backend: this measures the cross-process
host path.  Chip-to-chip moves need the ranks colocated in ONE process
(`Context.comm_set_colocated`; `python chip_smoke.py --chips 4`).
  python tools/bench_dataplane.py            # all rungs
  python tools/bench_dataplane.py --mb 16    # one rung
"""
import json
import multiprocessing as mp
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _prod_kernel(x):
    # module-level: the process-wide jit cache keys on kernel identity,
    # so every rep reuses one compiled executable (a per-rep lambda
    # re-traced and re-compiled EVERY rep — ~100 ms of setup charged to
    # each "transfer" in the old 118 ms/4 MiB baseline row)
    return x + 1.0


def _cons_kernel(x):
    return x * 1.0


def _worker(rank, nodes, port, mb, reps, q, transfer=False):
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"  # rank processes stay off the chip
        import parsec_tpu as pt
        from parsec_tpu.device import TpuDevice

        os.environ["PTC_MCA_comm_eager_limit"] = "65536"
        if transfer:
            os.environ["PTC_MCA_device_dp_transfer"] = "1"
        ctx = pt.Context(nb_workers=1)
        ctx.set_rank(rank, nodes)
        ctx.comm_init(port)
        elems = mb * (1 << 20) // 4
        esize = elems * 4
        arr = np.zeros((nodes, elems), dtype=np.float32)
        ctx.register_linear_collection("A", arr, elem_size=esize,
                                       nodes=nodes, myrank=rank)
        ctx.register_arena("t", esize)
        dev = TpuDevice(ctx)
        k = pt.L("k")
        times = []
        for rep in range(reps + 1):  # rep 0 = compile warmup
            tp = pt.Taskpool(ctx, globals={"R": rep})
            prod = tp.task_class("Prod")
            prod.param("k", 0, 0)
            prod.affinity("A", 0)
            cons = tp.task_class("Cons")
            cons.param("k", 0, 0)
            cons.affinity("A", 1)
            prod.flow("X", "RW", pt.In(pt.Mem("A", 0)),
                      pt.Out(pt.Ref("Cons", k, flow="X")))
            cons.flow("X", "R", pt.In(pt.Ref("Prod", k, flow="X")),
                      arena="t")
            cons.flow("Y", "W", pt.Out(pt.Mem("A", 1)), arena="t")
            dev.attach(prod, tp, kernel=_prod_kernel, reads=["X"],
                       writes=["X"], shapes={"X": (elems,)},
                       dtype=np.float32)
            dev.attach(cons, tp, kernel=_cons_kernel, reads=["X"],
                       writes=["Y"], shapes={"X": (elems,), "Y": (elems,)},
                       dtype=np.float32)
            ctx.comm_fence()  # both ranks ready: isolate the transfer
            t0 = time.perf_counter()
            tp.run()
            tp.wait()
            ctx.comm_fence()
            dt = time.perf_counter() - t0
            if rep == 0:
                base = dict(dev.stats)  # exclude compile-warmup traffic
            else:
                times.append(dt)
        end = dict(dev.stats)
        st = {k: (end.get(k, 0) - base.get(k, 0)) / reps
              for k in ("d2h_bytes", "h2d_bytes")}
        dev.stop()
        ctx.comm_fini()
        ctx.destroy()
        st["dp_xfer_bytes"] = (end.get("dp_xfer_bytes", 0)
                               - base.get("dp_xfer_bytes", 0)) / reps
        q.put(("ok", rank, min(times), st["d2h_bytes"], st["h2d_bytes"],
               st["dp_xfer_bytes"]))
    except Exception:
        import traceback
        q.put(("err", rank, traceback.format_exc(), 0, 0, 0))


def run_rung(mb, port, reps=3, transfer=False):
    mpctx = mp.get_context("spawn")
    q = mpctx.Queue()
    procs = [mpctx.Process(target=_worker,
                           args=(r, 2, port, mb, reps, q, transfer))
             for r in range(2)]
    for p in procs:
        p.start()
    try:
        res = [q.get(timeout=1200) for _ in range(2)]
    finally:
        # a wedged rank must not orphan children holding the rung's ports
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    errs = [r for r in res if r[0] != "ok"]
    if errs:
        raise RuntimeError(str(errs))
    wall = max(r[2] for r in res)  # transfer completes on the slower side
    xfer_b = sum(r[5] for r in res)
    return {
        "tile_mb": mb,
        "path": "transfer" if transfer else "bytes",
        # what actually moved the payload: a pull-incapable PJRT (probe
        # failed) degrades a requested transfer run to bytes — report it
        "path_taken": "transfer" if xfer_b > 0 else "bytes",
        "xfer_ms": round(wall * 1e3, 2),
        "gbps": round(mb / 1024 / wall * 8, 3),
        "d2h_bytes": sum(r[3] for r in res),
        "h2d_bytes": sum(r[4] for r in res),
        "dp_xfer_bytes": xfer_b,
    }


def main():
    mbs = [1, 4, 16, 64]
    if "--mb" in sys.argv:
        mbs = [int(sys.argv[sys.argv.index("--mb") + 1])]
    base = int(os.environ.get("PTC_PORT", "31100"))
    i = 0
    for mb in mbs:
        for transfer in (False, True):
            try:
                print(json.dumps(run_rung(mb, base + 2 * i,
                                          transfer=transfer)), flush=True)
            except Exception as e:
                print(json.dumps({"tile_mb": mb,
                                  "path": "transfer" if transfer
                                  else "bytes",
                                  "error": str(e)[:300]}), flush=True)
            i += 1


if __name__ == "__main__":
    main()
