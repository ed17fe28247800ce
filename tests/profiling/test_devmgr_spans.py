"""The device manager's spans: its loop as ready_wait / batch_wait /
dispatch in the profiler's trace and in the native ring, the clock that
maps the ring onto the profiler's, and nothing at all when both are
off."""
import glob
import time

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu import _native as N
from parsec_tpu.profiling import trace as T

CALL_KEYS = ("batches", "tasks", "batched_tasks", "spec_hits")


def calls(stats):
    """devmgr.calls_per_solve's count: batched waves plus tasks that ran
    alone and were not completed from a parked speculative result."""
    return stats["batches"] + stats["tasks"] - stats["batched_tasks"] \
        - stats["spec_hits"]


def spd(n, seed=0):
    m = np.random.default_rng(seed).standard_normal((n, n), np.float32)
    return m @ m.T + n * np.eye(n, dtype=np.float32)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tile build_potrf solve through spotrf_device (N=1024, NB=256)
    with the native ring on, under jax.profiler.start_trace, after an
    untraced warm-up solve."""
    import jax
    from parsec_tpu.algos import build_potrf
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device import bench_utils
    d = str(tmp_path_factory.mktemp("xprof"))
    a = spd(1024)
    with pytest.MonkeyPatch.context() as mp:
        # the CPU backend reports no bytes_limit
        mp.setattr(bench_utils, "hbm_bytes", lambda dev: 8 << 30)
        with pt.Context(nb_workers=2) as ctx:
            A = TwoDimBlockCyclic(1024, 1024, 256, 256, dtype=np.float32)
            A.from_dense(a)
            A.register(ctx, "A")
            dev = bench_utils.spotrf_device(ctx, A,
                                            jax_device=jax.devices()[0])
            tp = build_potrf(ctx, A, dev=dev)
            tp.run()
            tp.wait()
            A.from_dense(a)
            ctx.profile_enable(1)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(d, profiler_options=opts)
            # the idle manager sees the switches at its next empty poll
            time.sleep(0.2)
            s0 = dict(dev.stats)
            tp = build_potrf(ctx, A, dev=dev)
            tp.run()
            tp.wait()
            s1 = dict(dev.stats)
            tp_id = tp.tp_id
            # the manager pushes the last span's end after the tasks
            # complete: stop() joins it before both traces are read
            dev.stop()
            jax.profiler.stop_trace()
            ring = T.take_trace(ctx)
    f, = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    return {"spans": T.xplane_spans(f, "ptc.devmgr."),
            "delta": {k: s1[k] - s0[k] for k in CALL_KEYS},
            "tp": tp_id, "ring": ring}


def test_three_spans_tile_the_managers_thread(traced):
    spans = traced["spans"]
    assert {s[0] for s in spans} == set(T.DEVMGR_SPANS.values())
    assert len({s[3] for s in spans}) == 1      # one thread
    for a, b in zip(spans, spans[1:]):
        assert a[1] <= a[2] <= b[1], (a, b)     # never overlap
    for name, _, _, _, args in spans:
        assert args["chip"] == 0 and "qid" in args, (name, args)


def test_dispatch_spans_count_the_solves_tasks_and_calls(traced):
    disp = [s[4] for s in traced["spans"] if s[0] == "ptc.devmgr.dispatch"]
    delta = traced["delta"]
    assert delta["tasks"] == 20                 # POTRF, TRSM, SYRK, GEMM
    assert sum(a["tasks"] for a in disp) == delta["tasks"]
    assert sum(a["calls"] for a in disp) == calls(delta)
    assert {a["tp"] for a in disp} == {traced["tp"]}
    # every task a dispatch span takes, a wait span popped first
    waits = [s[4]["tasks"] for s in traced["spans"]
             if s[0] != "ptc.devmgr.dispatch"]
    assert sum(waits) == delta["tasks"]


def test_ring_keeps_the_same_spans_under_their_keys(traced):
    df = traced["ring"].to_pandas()
    loops = df[df["key"] == T.KEY_DEVICE_LOOP]
    disp = [s for s in traced["spans"] if s[0] == "ptc.devmgr.dispatch"]
    assert len(loops) == len(disp)
    assert list(loops["l0"]) == [s[4]["tasks"] for s in disp]
    assert list(loops["aux"]) == [s[4]["calls"] for s in disp]
    assert set(df["name"]) >= {"DEVICE_LOOP", "DEVICE_READY_WAIT",
                               "DEVICE_DISPATCH"}
    # each per-group dispatch span nests inside a loop span
    spans = list(zip(loops["begin_ns"], loops["end_ns"]))
    for b, e in zip(df[df["key"] == T.KEY_DEVICE]["begin_ns"],
                    df[df["key"] == T.KEY_DEVICE]["end_ns"]):
        assert any(lo <= b <= e <= hi for lo, hi in spans)


def test_shifted_ring_lands_inside_its_profiler_span(traced):
    disp = [s for s in traced["spans"] if s[0] == "ptc.devmgr.dispatch"]
    off, spread = T.profiler_offset_ns([(s[4]["ptc_ns"], s[1])
                                        for s in disp])
    assert 0 <= spread < 1_000_000
    df = traced["ring"].shift(off).to_pandas()
    assert traced["ring"].meta["profiler_offset_ns"] == off
    begins = sorted(df[df["key"] == T.KEY_DEVICE_LOOP]["begin_ns"])
    for b, (_, s, e, _, _) in zip(begins, disp):
        assert s <= b <= e, (s, b, e)


def test_no_sink_no_event(monkeypatch):
    """Trace level 0 and no profiler: the manager loop makes no
    ptc_prof_event call and builds no TraceMe; level 1 makes calls."""
    import jax
    from parsec_tpu.algos import build_potrf
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device import TpuDevice

    made, events = [], []

    class CountingTraceMe(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingTraceMe)
    monkeypatch.setattr(N.lib, "ptc_prof_event",
                        lambda *a: events.append(a))
    assert not CountingTraceMe.is_enabled()
    a = spd(256, seed=1)
    with pt.Context(nb_workers=2) as ctx:
        A = TwoDimBlockCyclic(256, 256, 64, 64, dtype=np.float32)
        A.from_dense(a)
        A.register(ctx, "A")
        dev = TpuDevice(ctx, jax_device=jax.devices()[0], prefetch=False)
        dev.batch_wait_ms = 2.0
        for level in (0, 1):
            ctx.profile_enable(level)
            A.from_dense(a)
            s0 = dev.stats["tasks"]
            tp = build_potrf(ctx, A, dev=dev)
            tp.run()
            tp.wait()
            dev.flush()
            assert dev.stats["tasks"] - s0 == 20
            if level == 0:
                # the solve staged its tiles in: DEVICE_H2D's path too
                assert dev.stats["h2d_bytes"] > 0
                assert events == [] and made == []
        dev.stop()
    keys = {e[1] for e in events}
    assert {T.KEY_READY_WAIT, T.KEY_DEVICE_LOOP, T.KEY_DEVICE} <= keys
    assert made == []


def test_traceme_args_from_the_name():
    assert T.traceme_args("ptc.devmgr.dispatch#chip=0,tp=3,x=a#") == \
        ("ptc.devmgr.dispatch", {"chip": 0, "tp": 3, "x": "a"})
    assert T.traceme_args("solve") == ("solve", {})


def test_profiler_offset_is_the_median_with_its_spread():
    pairs = [(1000, 1100), (2000, 2102), (3000, 3098), (4000, 4101),
             (5000, 9000)]
    assert T.profiler_offset_ns(pairs) == (101, 2)
    with pytest.raises(ValueError):
        T.profiler_offset_ns([])


def test_shift_moves_every_timestamp():
    ev = np.array([[T.KEY_DEVICE_LOOP, 0, 1, 2, 0, -1, 0, 10],
                   [T.KEY_DEVICE_LOOP, 1, 1, 2, 0, -1, 3, 25]])
    tr = T.Trace(ev.copy())
    assert tr.shift(-4) is tr
    assert list(tr.events[:, 7]) == [6, 21]
    assert tr.meta["profiler_offset_ns"] == -4
    assert [tr.dict.name(k) for k in (11, 12, 13)] == \
        ["DEVICE_READY_WAIT", "DEVICE_BATCH_WAIT", "DEVICE_LOOP"]
