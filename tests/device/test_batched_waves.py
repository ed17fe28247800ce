"""Batched device waves: bit-identity against the per-task path, the
dispatch loop's independence from the static planner, and the native
ready-front census.

The device manager dispatches each popped same-class group as one
vmapped program (`_dispatch_group`).  With `batch_max = 1` every pop
holds one task and each runs its own program (`_dispatch_one`); the two
must produce the same bits on every in-tree graph whose waves batch.
"""
import sys

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.data.collections import TwoDimBlockCyclic
from parsec_tpu.device import TpuDevice


def _spd(n, rng):
    x = rng.standard_normal((n, n)).astype(np.float64)
    return (x @ x.T + n * np.eye(n)).astype(np.float32)


def _device(ctx, batched):
    dev = TpuDevice(ctx)
    dev.batch_wait_ms = 2.0  # coalesce whole waves per pop
    if not batched:
        dev.batch_max = 1
    return dev


def _finish(ctx, dev, tp):
    tp.run()
    tp.wait()
    dev.flush()
    st = dict(dev.stats)
    dev.stop()
    return st


# -------------------------------------------------- bit-exact matrix
def _potrf_run(batched, N=128, nb=8):
    """potrf at the NT=16 tiling (816 instances)."""
    rng = np.random.default_rng(11)
    M = _spd(N, rng)
    with pt.Context(nb_workers=2) as ctx:
        A = TwoDimBlockCyclic(N, N, nb, nb, dtype=np.float32)
        A.from_dense(M)
        A.register(ctx, "A")
        from parsec_tpu.algos import build_potrf
        dev = _device(ctx, batched)
        st = _finish(ctx, dev, build_potrf(ctx, A, dev=dev))
        out = np.tril(A.to_dense()).copy()
    return out, st


def _rms_norm_run(batched, R=6, T=8, d=16):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(R * T, d)).astype(np.float32)
    w = rng.normal(size=(1, d)).astype(np.float32)
    with pt.Context(nb_workers=2) as ctx:
        Xc = TwoDimBlockCyclic(R * T, d, T, d, dtype=np.float32)
        Wc = TwoDimBlockCyclic(1, d, 1, d, dtype=np.float32)
        Oc = TwoDimBlockCyclic(R * T, d, T, d, dtype=np.float32)
        from parsec_tpu.ops.rms_norm import build_rms_norm
        dev = _device(ctx, batched)
        tp = build_rms_norm(ctx, Xc, Wc, Oc, dev=dev)
        Xc.from_dense(x)
        Wc.from_dense(w)
        st = _finish(ctx, dev, tp)
        out = Oc.to_dense().copy()
    return out, st


def _flash_attention_run(batched, NQ=6, T=8, d=16):
    rng = np.random.default_rng(6)
    L = NQ * T
    q = rng.normal(size=(L, d)).astype(np.float32)
    k = rng.normal(size=(L, d)).astype(np.float32)
    v = rng.normal(size=(L, d)).astype(np.float32)
    with pt.Context(nb_workers=2) as ctx:
        Qc = TwoDimBlockCyclic(L, d, T, d, dtype=np.float32)
        Kc = TwoDimBlockCyclic(L, d, L, d, dtype=np.float32)
        Vc = TwoDimBlockCyclic(L, d, L, d, dtype=np.float32)
        Oc = TwoDimBlockCyclic(L, d, T, d, dtype=np.float32)
        from parsec_tpu.ops.flash_attention import build_flash_attention
        dev = _device(ctx, batched)
        tp = build_flash_attention(ctx, Qc, Kc, Vc, Oc, dev=dev)
        Qc.from_dense(q)
        Kc.from_dense(k)
        Vc.from_dense(v)
        st = _finish(ctx, dev, tp)
        out = Oc.to_dense().copy()
    return out, st


@pytest.mark.parametrize("runner", [_potrf_run, _rms_norm_run,
                                    _flash_attention_run],
                         ids=["potrf_nt16", "rms_norm",
                              "flash_attention"])
def test_bit_exactness_matrix(runner):
    """Batched waves vs the per-task path bit-identical, with batched
    launches asserted on the batched side and none on the other."""
    out1, st1 = runner(True)
    out0, st0 = runner(False)
    assert out1.tobytes() == out0.tobytes()
    assert st1["batches"] > 0 and st1["batched_tasks"] > st1["batches"], \
        st1
    assert st0["batches"] == 0 and st0["batched_tasks"] == 0, st0
    assert st1["tasks"] == st0["tasks"]


# ------------------------------------------- dispatch never plans
def _potrf_tiles(ctx, dev):
    rng = np.random.default_rng(2)
    N, nb = 64, 8
    A = TwoDimBlockCyclic(N, N, nb, nb, dtype=np.float32)
    A.from_dense(_spd(N, rng))
    A.register(ctx, "A")
    from parsec_tpu.algos import build_potrf
    return build_potrf(ctx, A, dev=dev)


def _potrf_panels(ctx, dev):
    N, nb = 64, 8
    spd = _spd(N, np.random.default_rng(3))
    A = TwoDimBlockCyclic(N, N, N, nb, dtype=np.float32)
    for j in range(A.nt):
        A.tile(0, j)[...] = spd[:, j * nb:(j + 1) * nb]
    A.register(ctx, "A")
    from parsec_tpu.algos import build_potrf_panels
    return build_potrf_panels(ctx, A, dev=dev)


def _gemm(ctx, dev):
    rng = np.random.default_rng(7)
    N, nb, K = 32, 8, 64
    A = TwoDimBlockCyclic(N, K, nb, nb, dtype=np.float32)
    B = TwoDimBlockCyclic(K, N, nb, nb, dtype=np.float32)
    C = TwoDimBlockCyclic(N, N, nb, nb, dtype=np.float32)
    A.from_dense(rng.standard_normal((N, K), dtype=np.float32))
    B.from_dense(rng.standard_normal((K, N), dtype=np.float32))
    C.from_dense(np.zeros((N, N), np.float32))
    for coll, name in ((A, "A"), (B, "B"), (C, "C")):
        coll.register(ctx, name)
    from parsec_tpu.algos.gemm import build_gemm
    return build_gemm(ctx, A, B, C, dev=dev)


@pytest.mark.parametrize("build", [_potrf_tiles, _potrf_panels, _gemm],
                         ids=["potrf_tiles", "potrf_panels", "gemm"])
def test_dispatch_never_plans(build, monkeypatch):
    """The device manager runs no static analysis: extracting a flow
    graph walks the whole DAG on the manager's thread while the device
    waits, so a taskpool run through a TpuDevice extracts none."""
    from parsec_tpu.analysis import flowgraph
    orig = flowgraph.extract_flowgraph
    calls = []

    def counting(tp):
        calls.append(tp)
        return orig(tp)

    # every module that bound the function by name, not just its home
    for mod in list(sys.modules.values()):
        if getattr(mod, "__name__", "").startswith("parsec_tpu") \
                and getattr(mod, "extract_flowgraph", None) is orig:
            monkeypatch.setattr(mod, "extract_flowgraph", counting)
    with pt.Context(nb_workers=2) as ctx:
        dev = _device(ctx, True)
        st = _finish(ctx, dev, build(ctx, dev))
    assert st["batches"] > 0, st
    assert calls == []


# ------------------------------------------------------ front census
def test_device_peek_front_census():
    """The wave-granular native census: queued device tasks report
    their class ids without popping or pinning anything."""
    with pt.Context(nb_workers=2) as ctx:
        src = np.arange(6 * 16, dtype=np.float32).reshape(6, 16)
        tb = 16 * 4
        ctx.register_linear_collection("T", src, elem_size=tb)
        ctx.register_arena("t", tb)
        dev = TpuDevice(ctx, autostart=False)  # queue fills, no drain
        tp = pt.Taskpool(ctx, globals={"NT": 5})
        kv = pt.L("k")
        tc = tp.task_class("Census")
        tc.param("k", 0, pt.G("NT"))
        tc.flow("X", "RW", pt.In(pt.Mem("T", kv)),
                pt.Out(pt.Mem("T", kv)), arena="t")
        dev.attach(tc, tp, kernel=lambda x: x * 2.0, reads=["X"],
                   writes=["X"], shapes={"X": (16,)}, dtype=np.float32)
        tp.run()
        import time
        deadline = time.time() + 10.0
        front = []
        while time.time() < deadline:
            front = ctx.device_peek_front(dev.qid)
            if len(front) == 6:
                break
            time.sleep(0.01)
        assert len(front) == 6, front
        assert {cid for cid, _tp in front} == {tc.id}
        assert {tpp for _cid, tpp in front} == {tp._ptr}
        dev.start()  # drain so the pool completes
        tp.wait()
        dev.flush()
        np.testing.assert_allclose(
            src, np.arange(6 * 16, dtype=np.float32).reshape(6, 16) * 2)
        dev.stop()
