"""ptc-tune coverage: simulator determinism + simulated-vs-measured
conformance (diamond and potrf NT=16, both seeded from recorded
histograms), tuner proposal determinism, persistence round-trip +
Taskpool.run(tuned=) auto-apply, the knob snapshot/restore fix
(two pools, different knobs, no leak), graph signatures, and the
runtime magazine-batch knob."""
import json
import os

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.analysis import (CostModel, ScheduleSimulator, TuneStore,
                                 graph_signature, host_fingerprint,
                                 plan_taskpool)
from parsec_tpu.analysis.tune import (TUNE_KNOBS, apply_knobs, autotune,
                                      default_knobs, knob_env,
                                      price_collective,
                                      propose_collective, resolve_tuned)
from parsec_tpu.data.collections import TwoDimBlockCyclic
from parsec_tpu.utils import params as _mca


def _potrf(ctx, nt=6, nb=8):
    from parsec_tpu.algos.potrf import build_potrf
    A = TwoDimBlockCyclic(nt * nb, nt * nb, nb, nb, dtype=np.float32)
    A.register(ctx, "A")
    return A, build_potrf(ctx, A)


def _spd(A, nt, nb, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((nt * nb, nt * nb)).astype(np.float32)
    A.from_dense(M @ M.T + nt * nb * np.eye(nt * nb, dtype=np.float32))


# ----------------------------------------------------------- simulator
def test_simulator_deterministic_and_monotone():
    """Same inputs -> bit-identical results, twice; and the modeled
    dispatch overhead shrinks with a bigger magazine batch (the knob's
    direction on a dispatch-bound DAG)."""
    with pt.Context(nb_workers=1) as ctx:
        _A, tp = _potrf(ctx)
        plan = tp.plan()
    sim = ScheduleSimulator(plan, workers=2)
    a, b = sim.simulate(), sim.simulate()
    assert a == b
    assert a["makespan_ns"] > 0 and a["tasks"] == 56
    small = sim.simulate({"runtime.mag_batch": 8})
    big = sim.simulate({"runtime.mag_batch": 512})
    assert big["dispatch_ns_per_task"] < small["dispatch_ns_per_task"]
    assert big["makespan_ns"] < small["makespan_ns"]


def _fan_plan():
    """Device chores in homogeneous waves of 8: every wave certifies."""
    with pt.Context(nb_workers=1) as ctx:
        ctx.register_arena("t", 64)
        tp = pt.Taskpool(ctx, globals={"NB": 7, "KT": 3})
        k, b = pt.L("k"), pt.L("b")
        tc = tp.task_class("Fan")
        tc.param("b", 0, pt.G("NB"))
        tc.param("k", 0, pt.G("KT"))
        tc.flow("A", "RW",
                pt.In(None, guard=(k == 0)),
                pt.In(pt.Ref("Fan", b, k - 1, flow="A")),
                pt.Out(pt.Ref("Fan", b, k + 1, flow="A"),
                       guard=(k < pt.G("KT"))),
                arena="t")
        tc.body_device(0)
        return tp.plan()


def test_simulator_wave_fuse_pricing():
    """A certified fusable device wave runs as one batched launch, so
    each member is charged 1/width of the dispatch overhead.  On one
    worker the makespan is exactly the sum of the charges, and it is
    bit-deterministic."""
    plan = _fan_plan()
    assert plan.fusable_waves() > 0
    sim = ScheduleSimulator(plan, workers=1)
    assert sim.fused_width, "certified fusable device waves expected"
    assert set(sim.fused_width.values()) == {8}
    res = sim.simulate()
    assert res == sim.simulate()
    disp = res["dispatch_ns_per_task"]
    want = sum(sim.exec_ns[n] + disp / sim.fused_width.get(n, 1)
               for n in sim.nodes)
    assert res["makespan_ns"] == pytest.approx(want, rel=1e-12)
    # the same graph charged per task costs more
    flat = ScheduleSimulator(plan, workers=1)
    flat.fused_width = {}
    assert flat.simulate()["makespan_ns"] > res["makespan_ns"]
    assert "device.wave_fuse" not in TUNE_KNOBS
    assert "device.wave_fuse" not in sim.knob_axes()


def test_simulator_wave_fuse_axis_closed_without_certificates():
    """No device chores -> no certified wave -> every task keeps the
    full per-task dispatch charge: on one worker the makespan is the
    sum of exec time plus one whole charge per task."""
    with pt.Context(nb_workers=1) as ctx:
        _A, tp = _potrf(ctx)
        plan = tp.plan()
    sim = ScheduleSimulator(plan, workers=1)
    assert not sim.fused_width
    res = sim.simulate()
    want = sum(sim.exec_ns[n] + res["dispatch_ns_per_task"]
               for n in sim.nodes)
    assert res["makespan_ns"] == pytest.approx(want, rel=1e-12)


def test_simulator_workers_scale_work_bound():
    """A wide wave on 1 worker serializes; on 8 workers the simulated
    makespan drops toward the critical path."""
    with pt.Context(nb_workers=1) as ctx:
        _A, tp = _potrf(ctx)
        plan = tp.plan()
    one = ScheduleSimulator(plan, workers=1).simulate()
    many = ScheduleSimulator(plan, workers=8).simulate()
    assert many["makespan_ns"] < one["makespan_ns"]


def test_simulator_vs_measured_diamond():
    """Conformance on a hand-shaped diamond with real (sleepy) bodies:
    run it, seed the CostModel from the recorded histograms, and the
    simulated makespan must land within tolerance of the PR 5 executed
    critical path."""
    import time as _t

    from parsec_tpu.profiling import take_trace
    from parsec_tpu.profiling.critpath import critical_path

    def sleepy(ms):
        def body(t):
            _t.sleep(ms * 1e-3)
        return body

    def build(ctx):
        ctx.register_arena("t", 64)
        tp = pt.Taskpool(ctx)
        src = tp.task_class("Src")
        src.param("k", 0, 0)
        src.flow("X", "W", pt.Out(pt.Ref("Mid", 0, flow="X")),
                 pt.Out(pt.Ref("Mid", 1, flow="X")), arena="t")
        src.body(sleepy(2), pure=True)
        mid = tp.task_class("Mid")
        mid.param("j", 0, 1)
        mid.flow("X", "READ", pt.In(pt.Ref("Src", 0, flow="X")),
                 arena="t")
        mid.flow("Y", "W", pt.Out(pt.Ref("Sink", 0, flow="Y")),
                 arena="t")
        mid.body(sleepy(5), pure=True)
        sink = tp.task_class("Sink")
        sink.param("k", 0, 0)
        sink.flow("Y", "CTL",
                  pt.In(pt.Ref("Mid", pt.Range(0, 1), flow="Y")))
        sink.body(sleepy(2), pure=True)
        return tp

    with pt.Context(nb_workers=2) as ctx:
        tp = build(ctx)
        ctx.profile_enable(2)
        tp.run()
        tp.wait()
        cost = CostModel.from_context(ctx)
        assert cost is not None and cost.source == "metrics"
        trace = take_trace(ctx)
        plan = plan_taskpool(tp, cost=cost)
    executed = critical_path(trace)["total_ns"]
    sim = ScheduleSimulator(plan, cost=cost, workers=2).simulate()
    assert executed > 0
    # executed critpath = Src + Mid + Sink ~ 9 ms; the simulator prices
    # the same chain from the same histograms — tolerance covers
    # quantile estimation (~6%) + 1-core scheduling noise
    ratio = sim["makespan_ns"] / executed
    assert 0.5 < ratio < 2.0, (sim, executed)


def test_simulator_vs_measured_potrf_nt16():
    """The acceptance conformance workload: potrf at the bench tile
    grid (NT=16, 816 instances) with real numpy bodies — simulated
    makespan from histogram-seeded costs within tolerance of the
    executed critical path."""
    from parsec_tpu.profiling import take_trace
    from parsec_tpu.profiling.critpath import critical_path
    nt, nb = 16, 8
    with pt.Context(nb_workers=2) as ctx:
        A, tp = _potrf(ctx, nt, nb)
        _spd(A, nt, nb)
        ctx.profile_enable(2)
        tp.run()
        tp.wait()
        cost = CostModel.from_context(ctx)
        assert cost is not None
        trace = take_trace(ctx)
        plan = plan_taskpool(tp, cost=cost)
    assert plan.stats["instances"] == 816
    executed = critical_path(trace)["total_ns"]
    assert executed > 0
    sim = ScheduleSimulator(plan, cost=cost, workers=2).simulate()
    # the simulated schedule can't beat the executed critical path by
    # more than the cost-model error, and on 2 workers it must not
    # blow past the serial work either; wide tolerance — 1-core CI box
    ratio = sim["makespan_ns"] / executed
    assert 0.2 < ratio < 5.0, (sim["makespan_ns"], executed)


# --------------------------------------------------------------- tuner
def test_proposals_deterministic_across_processes_inputs():
    """Same graph, two independent plans -> identical ranked proposals
    (no wall-clock or ordering dependence)."""
    runs = []
    for _ in range(2):
        with pt.Context(nb_workers=1) as ctx:
            _A, tp = _potrf(ctx)
            plan = tp.plan()
        sim = ScheduleSimulator(plan, workers=1)
        runs.append([(p["knobs"], p["predicted_ns"])
                     for p in sim.propose(topk=4)])
    assert runs[0] == runs[1]


def test_autotune_model_only_does_not_persist(tmp_path):
    _mca.set("tune.cache_path", str(tmp_path / "t.json"))
    try:
        with pt.Context(nb_workers=1) as ctx:
            _A, tp = _potrf(ctx)
            res = autotune(tp, measure=None)
        assert res["winner"]["source"] == "model-only"
        assert not res["persisted"]
        assert not os.path.exists(str(tmp_path / "t.json"))
        assert res["candidates"], "proposals missing"
    finally:
        _mca.unset("tune.cache_path")


def test_autotune_validate_persist_roundtrip_autoapply(tmp_path):
    """The full loop: fake deterministic measurements prefer a
    non-default magazine batch; the winner persists keyed by (graph
    signature, host fingerprint); a NEW pool built the same way
    auto-applies it via run(tuned=True); MCA state restores after."""
    store_path = str(tmp_path / "tuned.json")
    _mca.set("tune.cache_path", store_path)
    try:
        def measure(knobs):
            # deterministic preference: mag_batch 128 is "fastest"
            return 1.0 - 0.5 * (int(knobs["runtime.mag_batch"]) == 128)

        with pt.Context(nb_workers=1) as ctx:
            _A, tp = _potrf(ctx)
            sig = graph_signature(tp)
            res = autotune(tp, measure=measure, topk=4)
        assert res["persisted"] and os.path.exists(store_path)
        assert res["winner"]["knobs"]["runtime.mag_batch"] == 128
        assert res["winner"]["measured_s"] == 0.5
        # every validation run recorded the predicted-vs-measured ratio
        assert all(r["predicted_vs_wall"] is not None
                   for r in res["validated"])
        # raw store schema (the MIGRATION.md contract)
        doc = json.load(open(store_path))
        assert doc["version"] == 1
        rec = doc["entries"][sig][host_fingerprint()]
        assert rec["knobs"]["runtime.mag_batch"] == 128

        # auto-apply on a fresh, identically-built pool
        before = _mca.get("runtime.mag_batch")
        with pt.Context(nb_workers=1) as ctx:
            A2, tp2 = _potrf(ctx)
            _spd(A2, 6, 8)
            assert graph_signature(tp2) == sig
            assert resolve_tuned(tp2, True)["runtime.mag_batch"] == 128
            tp2.run(tuned=True)
            tp2.wait()
            assert tp2.tuned_applied["runtime.mag_batch"] == 128
            # restored the moment run() returned
            assert _mca.get("runtime.mag_batch") == before
            assert "PTC_MCA_runtime_mag_batch" not in os.environ
    finally:
        _mca.unset("tune.cache_path")


def test_run_tuned_noop_when_store_empty(tmp_path):
    _mca.set("tune.cache_path", str(tmp_path / "empty.json"))
    try:
        with pt.Context(nb_workers=1) as ctx:
            A, tp = _potrf(ctx)
            _spd(A, 6, 8)
            tp.run(tuned=True)
            tp.wait()
            assert tp.tuned_applied is None
    finally:
        _mca.unset("tune.cache_path")


def test_two_pools_different_knobs_no_leak():
    """The satellite fix pinned: knob overrides applied for one
    Taskpool.run are snapshot/restored — pool B sees ITS vector, a
    third untuned pool sees the defaults, and nothing leaks into the
    registry or the environment afterwards."""
    seen = {}

    class ProbePool(pt.Taskpool):
        def commit(self):
            seen[self._probe] = (_mca.get("comm.rails"),
                                 os.environ.get("PTC_MCA_comm_rails"))
            return super().commit()

    def chain(ctx, name):
        ctx.register_arena("t", 8)
        tp = ProbePool(ctx, globals={"NB": 3})
        tp._probe = name
        k = pt.L("k")
        tc = tp.task_class("Task")
        tc.param("k", 0, pt.G("NB"))
        tc.flow("A", "RW",
                pt.In(None, guard=(k == 0)),
                pt.In(pt.Ref("Task", k - 1, flow="A")),
                pt.Out(pt.Ref("Task", k + 1, flow="A"),
                       guard=(k < pt.G("NB"))), arena="t")
        tc.body_noop()
        return tp

    default = _mca.get("comm.rails")
    with pt.Context(nb_workers=1) as ctx:
        a = chain(ctx, "a")
        a.run(tuned={"comm.rails": 4})
        a.wait()
        assert _mca.get("comm.rails") == default  # restored immediately
        b = chain(ctx, "b")
        b.run(tuned={"comm.rails": 1})
        b.wait()
        c = chain(ctx, "c")
        c.run()
        c.wait()
    assert seen["a"] == (4, "4")
    assert seen["b"] == (1, "1")
    assert seen["c"] == (default, None)
    assert _mca.get("comm.rails") == default
    assert "PTC_MCA_comm_rails" not in os.environ


# ---------------------------------------------------------- signatures
def test_graph_signature_stable_and_sensitive():
    with pt.Context(nb_workers=1) as ctx:
        _A, tp1 = _potrf(ctx)
        s1 = graph_signature(tp1)
    with pt.Context(nb_workers=1) as ctx:
        _A, tp2 = _potrf(ctx)
        s2 = graph_signature(tp2)
    with pt.Context(nb_workers=1) as ctx:
        _A, tp3 = _potrf(ctx, nt=5)  # different problem size
        s3 = graph_signature(tp3)
    assert s1 == s2
    assert s1 != s3
    assert len(s1) == 16


def test_host_fingerprint_stable():
    assert host_fingerprint() == host_fingerprint()
    assert len(host_fingerprint()) == 16


# -------------------------------------------------------- knob plumbing
def test_apply_knobs_snapshot_restore_and_unknown():
    before = _mca.get("comm.chunk_size")
    with apply_knobs({"comm.chunk_size": 12345}):
        assert _mca.get("comm.chunk_size") == 12345
        assert os.environ["PTC_MCA_comm_chunk_size"] == "12345"
    assert _mca.get("comm.chunk_size") == before
    assert "PTC_MCA_comm_chunk_size" not in os.environ
    with pytest.raises(KeyError):
        with apply_knobs({"not.a.knob": 1}):
            pass
    assert _mca.get("comm.chunk_size") == before


def test_knob_env_spelling():
    env = knob_env({"comm.rails": 4, "coll.topo": "ring"})
    assert env == {"PTC_MCA_comm_rails": "4",
                   "PTC_MCA_coll_topo": "ring"}


def test_default_knobs_covers_registry():
    kv = default_knobs()
    assert set(kv) == set(TUNE_KNOBS)


def test_mag_batch_env_knob_reaches_native():
    """PTC_MCA_runtime_mag_batch binds at context creation: a tiny
    batch forces visible freelist refill traffic on a chain that a
    large one amortizes away; the chain completes correctly at both
    extremes."""
    for mag in ("4", "1024"):
        os.environ["PTC_MCA_runtime_mag_batch"] = mag
        try:
            with pt.Context(nb_workers=1) as ctx:
                ctx.register_arena("t", 8)
                tp = pt.Taskpool(ctx, globals={"NB": 999})
                k = pt.L("k")
                tc = tp.task_class("Task")
                tc.param("k", 0, pt.G("NB"))
                tc.flow("A", "RW",
                        pt.In(None, guard=(k == 0)),
                        pt.In(pt.Ref("Task", k - 1, flow="A")),
                        pt.Out(pt.Ref("Task", k + 1, flow="A"),
                               guard=(k < pt.G("NB"))), arena="t")
                tc.body_noop()
                tp.run()
                tp.wait()
                st = ctx.sched_stats()
            assert st["freelist_hits"] + st["freelist_misses"] > 0
        finally:
            os.environ.pop("PTC_MCA_runtime_mag_batch", None)


# ------------------------------------------------- collective proposals
def test_collective_model_prefers_fewer_slices_when_small():
    """The closed-form collective model: slicing a tiny message is
    pure overhead, so 1 slice prices below 16; the proposal list is
    deterministic and always carries the default vector."""
    small1 = price_collective({"coll.topo": "auto",
                               "coll.max_slices": 1}, 4096, 2)
    small16 = price_collective({"coll.topo": "auto",
                                "coll.max_slices": 16}, 4096, 2)
    assert small1 < small16
    p1 = propose_collective(2 << 20, 2)
    p2 = propose_collective(2 << 20, 2)
    assert p1 == p2
    dk = {"coll.topo": _mca.get("coll.topo"),
          "coll.max_slices": _mca.get("coll.max_slices"),
          "comm.eager_limit": _mca.get("comm.eager_limit")}
    assert any(r["knobs"] == dk for r in p1)
    # the fitted eager legs are cheaper than rendezvous at these sizes:
    # the model's top proposal raises the eager threshold so the
    # per-rank segment rides the cheap path (the lever the collective
    # bench's validation confirmed on this box)
    assert p1[0]["knobs"]["comm.eager_limit"] >= 1 << 20


def test_stream_model_dedupes_single_chunk_candidates():
    from parsec_tpu.analysis.tune import price_stream, propose_stream
    p = propose_stream(4 << 20, 8)
    assert p == propose_stream(4 << 20, 8)
    # no two proposals may be behaviorally identical (single-chunk
    # configs collapse the rails axis)
    keys = set()
    for r in p:
        chunk = r["knobs"]["comm.chunk_size"]
        nch = ((4 << 20) + chunk - 1) // chunk if (4 << 20) > chunk else 1
        k = (chunk, r["knobs"]["comm.rails"] if nch > 1 else 0)
        assert k not in keys
        keys.add(k)
    # chunking a payload costs envelopes: pricing is monotone there
    one = price_stream({"comm.chunk_size": 8 << 20, "comm.rails": 1},
                       4 << 20, 1)
    many = price_stream({"comm.chunk_size": 64 << 10, "comm.rails": 1},
                        4 << 20, 1)
    assert one < many
