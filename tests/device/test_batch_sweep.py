"""The device manager's accumulate sweep (`batch_wait_ms`): after a
multi-task drain it keeps popping while the engine still holds a task
that could land on the queue (Context.tasks_in_flight() > 0), bounded by
batch_wait_ms.  An all-device DAG has nothing in flight once a wave is
routed, so its sweeps close at once with the same batches; a wave whose
tasks are still being released by CPU bodies still lands whole."""
import time

import numpy as np

import parsec_tpu as pt
from parsec_tpu.algos import build_potrf
from parsec_tpu.data import TwoDimBlockCyclic
from parsec_tpu.device import TpuDevice


def _spd(n):
    rng = np.random.default_rng(5)
    m = rng.standard_normal((n, n), dtype=np.float32)
    return m @ m.T + n * np.eye(n, dtype=np.float32)


def _potrf(batch_wait_ms, n=256, nb=32):
    """A tiled Cholesky of NT = n / nb on the CPU device: factor, stats."""
    with pt.Context(nb_workers=2) as ctx:
        A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32)
        A.from_dense(_spd(n))
        A.register(ctx, "A")
        dev = TpuDevice(ctx, prefetch=False)
        dev.batch_wait_ms = batch_wait_ms
        tp = build_potrf(ctx, A, dev=dev)
        tp.run()
        tp.wait()
        dev.flush()
        st = dict(dev.stats)
        agg = ctx.device_stats()
        dev.stop()
        return np.tril(A.to_dense()), st, agg


def test_all_device_dag_sweeps_close_on_quiescence():
    f50, st, agg = _potrf(50.0)
    f0, st0, _ = _potrf(0.0)
    assert f50.tobytes() == f0.tobytes()
    assert st["sweeps"] > 0
    assert st["sweep_quiesced"] == st["sweeps"], st
    assert st["sweep_ns"] / st["sweeps"] < 5e6, st
    assert {k: agg[k] for k in ("sweeps", "sweep_hits", "sweep_quiesced",
                                "sweep_ns")} == \
        {k: st[k] for k in ("sweeps", "sweep_hits", "sweep_quiesced",
                            "sweep_ns")}
    # batch_wait_ms = 0: no sweep at all
    assert st0["sweeps"] == st0["sweep_ns"] == 0


def test_wave_released_by_sleeping_cpu_bodies_lands_in_one_batch():
    """P(k) (CPU) feeds D(k) (device).  P(0), P(1) return at once, the
    others sleep 0.1 k s on their own workers; the device starts once
    D(0) and D(1) are queued, so its first drain is a multi-task pop and
    its sweep must wait for D(2..5), then close on quiescence long
    before the deadline."""
    w, wait_ms = 6, 5000.0
    with pt.Context(nb_workers=w) as ctx:
        arr = np.zeros((w, 4), dtype=np.float32)
        ctx.register_linear_collection("A", arr, elem_size=16, nodes=1,
                                       myrank=0)
        ctx.register_arena("t", 16)
        dev = TpuDevice(ctx, autostart=False, prefetch=False)
        dev.batch_wait_ms = wait_ms
        tp = pt.Taskpool(ctx, globals={"W": w - 1})
        k = pt.L("k")
        p = tp.task_class("P")
        p.param("k", 0, pt.G("W"))
        p.flow("A", "RW", pt.In(pt.Mem("A", k)),
               pt.Out(pt.Ref("D", k, flow="A")), arena="t")

        def body(v):
            kk = v["k"]
            if kk >= 2:
                time.sleep(0.1 * kk)
            v.data("A", np.float32)[:] = kk

        p.body(body)
        d = tp.task_class("D")
        d.param("k", 0, pt.G("W"))
        d.flow("A", "RW", pt.In(pt.Ref("P", k, flow="A")),
               pt.Out(pt.Mem("A", k)), arena="t")
        dev.attach(d, tp, kernel=lambda x: x + 1.0, reads=["A"],
                   writes=["A"], shapes={"A": (4,)})
        tp.run()
        end = time.monotonic() + 30
        while len(ctx.device_peek_front(dev.qid)) < 2:
            assert time.monotonic() < end
            time.sleep(0.001)
        dev.start()
        tp.wait()
        dev.flush()
        st = dict(dev.stats)
        dev.stop()
    np.testing.assert_array_equal(
        arr, np.repeat(np.arange(1, w + 1, dtype=np.float32), 4)
        .reshape(w, 4))
    assert st["batches"] == 1 and st["batched_tasks"] == w, st
    assert st["sweeps"] == st["sweep_hits"] == 1, st
    assert st["sweep_quiesced"] == 1, st
    assert st["sweep_ns"] < wait_ms * 1e6, st
