"""Context.tasks_in_flight(): the engine's count of tasks handed to the
scheduler and not yet back from their execution.  The device manager's
accumulate sweep closes on it, so it must return to 0 once a pool has
drained under every scheduler module (with and without the same-worker
bypass slot), read > 0 while a body runs, and never go negative."""
import threading
import time

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.dsl import DtdTaskpool
from parsec_tpu.utils import params as _mca

from .chain_util import chain_task_class
from .test_sched_matrix import MODULES


def _settles_to_zero(ctx, timeout=10.0):
    """tp.wait() returns inside the last task's completion, a moment
    before its worker uncounts it: poll."""
    end = time.monotonic() + timeout
    while ctx.tasks_in_flight() != 0 and time.monotonic() < end:
        time.sleep(0.001)
    return ctx.tasks_in_flight() == 0


def _fan_chain(ctx, n=60):
    """A noop RW chain of n + 1 tasks and n independent noop tasks."""
    ctx.register_arena("t", 8)
    tp = pt.Taskpool(ctx, globals={"NB": n})
    chain_task_class(tp).body_noop()
    ep = tp.task_class("Ep")
    ep.param("k", 0, pt.G("NB"))
    ep.body_noop()
    return tp


@pytest.mark.parametrize("bypass", [True, False], ids=["bypass",
                                                       "no_bypass"])
@pytest.mark.parametrize("sched", MODULES)
def test_reads_zero_once_the_pool_drained(sched, bypass):
    _mca.set("sched.bypass", bypass)
    try:
        with pt.Context(nb_workers=2, scheduler=sched) as ctx:
            assert ctx.tasks_in_flight() == 0
            tp = _fan_chain(ctx)
            tp.run()
            tp.wait()
            st = ctx.sched_stats()
            assert _settles_to_zero(ctx), ctx.tasks_in_flight()
    finally:
        _mca.unset("sched.bypass")
    assert st["bypass_enabled"] is bypass
    assert sum(st["executed"]) == 2 * 61


def test_counts_a_task_while_its_body_runs():
    started, release = threading.Event(), threading.Event()
    with pt.Context(nb_workers=2) as ctx:
        tp = pt.Taskpool(ctx)
        tc = tp.task_class("Hold")
        tc.param("k", 0, 0)

        def body(v):
            started.set()
            assert release.wait(30)

        tc.body(body)
        tp.run()
        assert started.wait(30)
        try:
            assert ctx.tasks_in_flight() >= 1
        finally:
            release.set()
        tp.wait()
        assert _settles_to_zero(ctx)


def test_never_negative_across_a_dtd_and_a_ptg_pool():
    """A DTD chain and a PTG chain run at once on one context while a
    sampler reads the count as fast as it can."""
    seen, stop = [], threading.Event()

    with pt.Context(nb_workers=3) as ctx:
        def sample():
            while not stop.is_set():
                seen.append(ctx.tasks_in_flight())

        buf = np.zeros(1, dtype=np.int64)
        dtd = DtdTaskpool(ctx)
        tile = dtd.tile_of(ctx.data(0, buf))
        ptg = _fan_chain(ctx, n=200)
        sampler = threading.Thread(target=sample)
        sampler.start()
        try:
            ptg.run()

            def add1(v):
                v.data(0, np.int64)[0] += 1

            for _ in range(200):
                dtd.insert_task(add1, (tile, "INOUT"))
            dtd.wait()
            ptg.wait()
            assert _settles_to_zero(ctx)
        finally:
            stop.set()
            sampler.join()
        dtd.destroy()
    assert buf[0] == 200
    assert seen and min(seen) >= 0
    assert max(seen) > 0
