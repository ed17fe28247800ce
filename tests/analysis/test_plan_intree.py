"""Plan-baseline guard (the ptc-plan twin of test_verify_intree): every
in-tree graph generator plans CLEAN — no enumeration refusal at the
default tilings, finite residency and makespan bounds — and the potrf
bench tiling (NT=16, 816 instances) plans in under 5 s."""
import os
import sys
import time

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.analysis import plan_taskpool
from parsec_tpu.data.collections import TwoDimBlockCyclic

TOOLS = os.path.join(os.path.dirname(__file__), "..", "..", "tools")
sys.path.insert(0, os.path.abspath(TOOLS))

import plan_graphs  # noqa: E402


def _all_plans():
    return list(plan_graphs.plan_all())


def test_intree_graphs_plan_clean():
    plans = _all_plans()
    assert len(plans) >= 33
    names = {n for n, _ in plans}
    for expected in ("potrf", "gemm_dist", "moe", "ring_attention",
                     "ops_paged_decode", "ops_paged_prefill_warm",
                     "ops_paged_spec_verify", "coll_reduce_ring",
                     "coll_fanout", "ops_tp_paged_decode",
                     "ops_tp_paged_verify"):
        assert any(expected in n for n in names), names
    dirty = {n: plan_graphs.plan_issues(p) for n, p in plans
             if plan_graphs.plan_issues(p)}
    assert not dirty, f"in-tree graphs with plan issues: {dirty}"
    # every plan is finite and internally consistent
    for _n, p in plans:
        assert not p.bounded
        assert p.est_bytes() is not None and p.est_bytes() > 0
        for r, row in p.per_rank.items():
            assert 0 <= row["live_peak_bytes"] <= row["peak_bytes"]
            assert row["device_peak_bytes"] <= row["peak_bytes"]


def test_intree_fusability_verdicts_complete():
    """Every (rank, wave) of every in-tree graph carries an EXPLICIT
    certify/refuse verdict (no silent skips — the ISSUE 12 acceptance
    bar), refusals always carry a reason, and the pure-body tile DAGs
    certify nonzero fusable waves (the mega-kernel prep artifact)."""
    plans = _all_plans()
    counts = {}
    for name, p in plans:
        waves = {(r, row["wave"]) for r, rows in p.waves.items()
                 for row in rows}
        certified = {(c["rank"], c["wave"]) for c in p.fusability}
        assert waves == certified, f"{name}: waves without a verdict"
        for c in p.fusability:
            assert isinstance(c["fusable"], bool)
            if not c["fusable"]:
                assert c["reasons"], f"{name}: refusal without reason"
            else:
                assert c["homogeneous"] and c["claimed"]
                assert c["tile_sig"] is not None
        counts[name] = p.fusable_waves()
    # locked: the declared-pure tile DAGs certify their homogeneous
    # waves (potrf: every homogeneous wave of the 3(NT-1)+1 schedule)
    assert counts["potrf"] >= 10
    assert counts["gemm"] == 4
    assert counts["gemm_dist"] == 4
    assert counts["ops_rms_norm"] == 1
    assert counts["ops_flash_attention"] == 1
    assert sum(counts.values()) >= 30


def test_potrf_bench_tiling_under_5s():
    dt_ms = plan_graphs.potrf_nt16_ms()
    assert dt_ms < plan_graphs.POTRF_NT16_BUDGET_S * 1e3, \
        f"ptc-plan took {dt_ms:.0f} ms on potrf NT=16"


def test_plan_graphs_driver_json(tmp_path):
    """The make plan-graphs driver exits 0 on a subset and writes the
    JSON schema bench_check's potrf_nt16_ms row reads."""
    out = tmp_path / "plan.json"
    assert plan_graphs.main(["gemm", "moe", "--json", str(out)]) == 0
    import json
    doc = json.loads(out.read_text())
    assert set(doc["graphs"]) == {"gemm", "moe"}
    for row in doc["graphs"].values():
        assert row["issues"] == []
        assert row["peak_bytes"] > 0
        assert row["certified_waves"] == row["waves"]
    # the per-graph fusable-wave count bench_check-visible baseline
    assert doc["graphs"]["gemm"]["fusable_waves"] == 4
    assert doc["graphs"]["moe"]["fusable_waves"] == 0


@pytest.mark.slow
def test_potrf_large_grid_headroom():
    """NT=32: 4x the bench instance count still plans comfortably."""
    from parsec_tpu.algos.potrf import build_potrf
    with pt.Context(nb_workers=1) as ctx:
        A = TwoDimBlockCyclic(32 * 8, 32 * 8, 8, 8, dtype=np.float32)
        A.register(ctx, "A")
        tp = build_potrf(ctx, A)
        t0 = time.perf_counter()
        plan = plan_taskpool(tp)
        dt = time.perf_counter() - t0
    assert not plan.bounded
    assert dt < 30.0
