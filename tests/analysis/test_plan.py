"""ptc-plan unit coverage: liveness/wave schedule, datum chains, comm
volume with rank mapping, makespan bounds under a seeded cost model,
spill prediction, and the symbolic interval fallback."""
import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.analysis import CostModel, plan_taskpool
from parsec_tpu.data.collections import TwoDimBlockCyclic


def _gemm(ctx, m=128, n=128, k=32, mb=16, dist=False, nodes=1):
    from parsec_tpu.algos.gemm import build_gemm, build_gemm_dist
    kw = dict(dtype=np.float32)
    if nodes > 1:
        kw.update(nodes=nodes, P=nodes, Q=1, myrank=0)
    A = TwoDimBlockCyclic(m, k, mb, mb, **kw)
    B = TwoDimBlockCyclic(k, n, mb, mb, **kw)
    C = TwoDimBlockCyclic(m, n, mb, mb, **kw)
    A.register(ctx, "A")
    B.register(ctx, "B")
    C.register(ctx, "C")
    build = build_gemm_dist if dist else build_gemm
    return A, B, C, build(ctx, A, B, C)


def test_gemm_residency_exact():
    """Single-rank GEMM: the no-eviction working set equals the full
    tile set exactly; the liveness floor is below it (A/B panels die
    wave to wave while C lives throughout)."""
    with pt.Context(nb_workers=1) as ctx:
        m = n = 128
        k, mb = 32, 16
        A, B, C, tp = _gemm(ctx, m, n, k, mb)
        plan = tp.plan()
    tile_set = (m * k + k * n + m * n) * 4
    assert not plan.bounded
    assert plan.peak_bytes() == tile_set
    assert plan.est_bytes() == tile_set
    assert 0 < plan.live_peak_bytes() < tile_set
    # k-chain depth = KT+1 waves
    assert plan.stats["waves"] == k // mb
    # chain pools: comm-free on one rank
    assert plan.comm_bytes() == 0


def test_gemm_spill_prediction_iff_over_budget():
    """predict_spills > 0 exactly when the budget is below the working
    set (the acceptance iff): half budget spills, full budget doesn't."""
    with pt.Context(nb_workers=1) as ctx:
        _A, _B, _C, tp = _gemm(ctx)
        plan = tp.plan()
    tile_set = plan.peak_bytes()
    assert plan.predict_spills(tile_set // 2, 0, device_only=False) > 0
    assert plan.predict_spills(tile_set, 0, device_only=False) == 0
    assert plan.predict_spills(4 << 30, 0, device_only=False) == 0


def test_wave_decomposition_potrf():
    """Waves are ready fronts grouped by class (the MPK-prep artifact):
    potrf's first wave is the lone POTRF (homogeneous), the third mixes
    GEMM and SYRK (heterogeneous)."""
    from parsec_tpu.algos.potrf import build_potrf
    with pt.Context(nb_workers=1) as ctx:
        A = TwoDimBlockCyclic(6 * 8, 6 * 8, 8, 8, dtype=np.float32)
        A.register(ctx, "A")
        plan = plan_taskpool(build_potrf(ctx, A))
    rows = plan.waves[0]
    assert rows[0]["classes"] == {"POTRF": 1}
    assert rows[0]["homogeneous"]
    assert set(rows[2]["classes"]) == {"GEMM", "SYRK"}
    assert not rows[2]["homogeneous"]
    assert sum(r["tasks"] for r in rows) == plan.stats["instances"]
    # live bytes tracked per wave, never above the rank peak
    assert all(0 <= r["live_bytes"] <=
               plan.per_rank[0]["peak_bytes"] for r in rows)
    assert max(r["live_bytes"] for r in rows) == \
        plan.per_rank[0]["live_peak_bytes"]


def test_comm_volume_rank_mapping():
    """2-rank-shaped gemm_dist (P=2): A panels never cross (ReadA is
    placed at A's owner = Gemm row's rank), every B tile crosses once —
    the per-edge byte map is exact and symmetric, and everything rides
    eager at these tile sizes."""
    with pt.Context(nb_workers=1) as ctx:
        nt, mb = 4, 96
        _A, _B, _C, tp = _gemm(ctx, nt * mb, nt * mb, nt * mb, mb,
                               dist=True, nodes=2)
        plan = tp.plan()
    tile = mb * mb * 4
    # per (k, n): one remote rank -> kt*nt transfers split evenly
    expect = (nt * nt // 2) * tile
    assert plan.edges_bytes == {(0, 1): expect, (1, 0): expect}
    for r in (0, 1):
        row = plan.per_rank[r]
        assert row["comm_out_bytes"] == expect
        assert row["comm_in_bytes"] == expect
        assert row["comm_out_msgs"] == nt * nt // 2
        assert row["eager_bytes"] == expect and row["rdv_bytes"] == 0
        assert plan.wire_out_bound(r) > expect
    assert plan.eager_limit > tile


def test_makespan_seeded_cost_model():
    """Diamond DAG under an explicit cost table: the critical path is
    the hand-computed slow leg, work/p the serial sum on one worker."""
    with pt.Context(nb_workers=1) as ctx:
        ctx.register_arena("t", 64)
        tp = pt.Taskpool(ctx)
        src = tp.task_class("Src")
        src.param("k", 0, 0)
        src.flow("X", "W",
                 pt.Out(pt.Ref("Mid", 0, flow="X")),
                 pt.Out(pt.Ref("Mid", 1, flow="X")), arena="t")
        mid = tp.task_class("Mid")
        mid.param("j", 0, 1)
        mid.flow("X", "READ", pt.In(pt.Ref("Src", 0, flow="X")),
                 arena="t")
        mid.flow("Y", "W", pt.Out(pt.Ref("Sink", 0, flow="Y")),
                 arena="t")
        sink = tp.task_class("Sink")
        sink.param("k", 0, 0)
        sink.flow("Y", "CTL",
                  pt.In(pt.Ref("Mid", pt.Range(0, 1), flow="Y")))
        cost = CostModel({"Src": 100, "Mid": 1000, "Sink": 10},
                         source="test")
        plan = plan_taskpool(tp, cost=cost)
    m = plan.makespan
    assert m["cost_source"] == "test"
    assert m["critical_path_ns"] == 100 + 1000 + 10
    assert m["path_len"] == 3
    # 1 worker: work bound = serial sum = 100 + 2*1000 + 10
    assert m["work_ns"] == 100 + 2 * 1000 + 10
    assert m["lower_bound_ns"] == m["work_ns"]
    assert plan.stats["waves"] == 3


def test_cost_model_json_roundtrip(tmp_path):
    p = tmp_path / "prof.json"
    p.write_text('{"classes": {"Gemm": 5000.0}, "default_ns": 250}')
    cm = CostModel.from_json(str(p))
    assert cm.ns("Gemm") == 5000.0
    assert cm.ns("Other") == 250
    assert cm.source == str(p)
    assert CostModel(cm.to_json()["classes"]).ns("Gemm") == 5000.0


def test_symbolic_fallback_bounds_residency():
    """Enumeration refused (tiny max_instances): the plan degrades to
    the interval residency bound — finite, >= the exact working set —
    with an explicit note; waves/comm/makespan are absent."""
    with pt.Context(nb_workers=1) as ctx:
        _A, _B, _C, tp = _gemm(ctx)
        exact = tp.plan().peak_bytes()
        plan = tp.plan(max_instances=10)
    assert plan.bounded
    assert plan.est_bytes() is not None
    assert plan.est_bytes() >= exact
    assert any("refused" in n for n in plan.notes)
    assert plan.makespan == {}
    assert plan.predict_spills(1, 0) == 0  # inconclusive, never lies
    # text/json render in both modes
    assert "SYMBOLIC" in plan.text()
    assert plan.to_json()["bounded"] is True


def test_plan_text_and_json_render():
    with pt.Context(nb_workers=1) as ctx:
        _A, _B, _C, tp = _gemm(ctx)
        plan = tp.plan()
    txt = plan.text(waves=True)
    assert "peak" in txt and "wave" in txt
    doc = plan.to_json()
    import json
    json.dumps(doc)
    assert doc["est_bytes"] == plan.est_bytes()
    assert doc["makespan"]["lower_bound_ns"] > 0


def test_plan_cli_intree():
    import os
    import sys
    tools = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", "tools"))
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import ptc_plan
    assert ptc_plan.main(["gemm"]) == 0


def test_predicted_vs_executed_critpath():
    """The first-class regression signal: plan the pool, run it under
    level-2 tracing, seed the cost model from the always-on histograms,
    and compare the predicted critical path against the PR 5 executed
    one.  The predicted path's structure is deterministic (potrf's
    3*(NT-1)+1 chain); the ns comparison stays loose — this is a
    1-core CI box."""
    from parsec_tpu.algos.potrf import build_potrf
    from parsec_tpu.analysis import compare_critpath
    from parsec_tpu.profiling import take_trace
    nt = 6
    with pt.Context(nb_workers=2) as ctx:
        ctx.profile_enable(True)
        A = TwoDimBlockCyclic(nt * 8, nt * 8, 8, 8, dtype=np.float32)
        A.register(ctx, "A")
        rng = np.random.default_rng(0)
        M = rng.standard_normal((nt * 8, nt * 8)).astype(np.float32)
        A.from_dense(M @ M.T + nt * 8 * np.eye(nt * 8, dtype=np.float32))
        tp = build_potrf(ctx, A)
        tp.run()
        tp.wait()
        cost = CostModel.from_context(ctx)
        assert cost is not None and cost.source == "metrics"
        assert all(cost.ns(c) > 0 for c in ("POTRF", "TRSM", "GEMM"))
        plan = plan_taskpool(tp, cost=cost)
        cmp = compare_critpath(plan, take_trace(ctx))
    assert cmp["predicted_path_len"] == 3 * (nt - 1) + 1
    assert cmp["executed_path_len"] > 0
    assert cmp["predicted_ns"] > 0 and cmp["executed_ns"] > 0
    assert cmp["ratio"] is not None and cmp["cost_source"] == "metrics"
