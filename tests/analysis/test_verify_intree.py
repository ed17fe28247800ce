"""Clean-baseline guard: ptc-verify reports ZERO findings across every
in-tree graph generator (tools/verify_graphs.py), and completes on the
largest in-tree graph (potrf at the bench tiling, N=16384 NB=1024 ->
16x16 tiles, the BASELINE rung-5 r2 grid) in under 5 s."""
import os
import sys
import time

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.analysis import verify_taskpool
from parsec_tpu.data.collections import TwoDimBlockCyclic

TOOLS = os.path.join(os.path.dirname(__file__), "..", "..", "tools")
sys.path.insert(0, os.path.abspath(TOOLS))

import verify_graphs  # noqa: E402


def _all_reports():
    return list(verify_graphs.verify_all())


def test_intree_graphs_verify_clean():
    reports = _all_reports()
    # every generator actually built and verified (ptc-shard raised the
    # floor: 33 graphs with the tp sharded decode/verify pair)
    assert len(reports) >= 33
    names = {n for n, _ in reports}
    for expected in ("potrf", "potrf_panels", "gemm_dist", "geqrf",
                     "moe", "ring_attention", "ops_rms_norm",
                     "ops_flash_attention", "ops_paged_decode",
                     "ops_paged_prefill", "ops_paged_prefill_warm",
                     "ops_paged_spec_verify", "coll_reduce_ring",
                     "coll_fanout", "ops_tp_paged_decode",
                     "ops_tp_paged_verify"):
        assert any(expected in n for n in names), names
    dirty = {n: [repr(f) for f in r.findings]
             for n, r in reports if not r.ok()}
    assert not dirty, f"in-tree graphs with findings: {dirty}"
    # none degraded to symbolic-only silently
    assert all(not r.stats.get("bounded") for _, r in reports)


def test_intree_coverage_exercises_instances():
    reports = _all_reports()
    total = sum(r.stats.get("instances", 0) for _, r in reports)
    edges = sum(r.stats.get("edges", 0) for _, r in reports)
    assert total > 500 and edges > 500


def test_potrf_bench_tiling_under_5s():
    nt, nb = 16, 1024  # N=16384, NB=1024 (BASELINE rung-5 r2 config)
    from parsec_tpu.algos.potrf import build_potrf
    with pt.Context(nb_workers=1) as ctx:
        # verification cost depends only on the TILE GRID (nt x nt);
        # back it with 8-wide tiles so the array stays tiny while the
        # execution space is the bench one
        A = TwoDimBlockCyclic(nt * 8, nt * 8, 8, 8, dtype=np.float32)
        A.register(ctx, "A")
        tp = build_potrf(ctx, A)
        t0 = time.perf_counter()
        report = verify_taskpool(tp)
        dt = time.perf_counter() - t0
    assert report.ok(), report.text()
    # the full NT=16 DAG: 16 POTRF + 120 TRSM + 120 SYRK + 560 GEMM
    assert report.stats["instances"] == 816
    assert dt < 5.0, f"ptc-verify took {dt:.2f}s on potrf NT={nt}"
    del nb  # documents the bench NB; tiles above are shrunk on purpose


def test_ptc_verify_cli_intree():
    import ptc_verify
    assert ptc_verify.main(["potrf"]) == 0


@pytest.mark.slow
def test_potrf_large_grid_headroom():
    """NT=32 (N=32768 at NB=1024): 4x the bench instance count still
    verifies comfortably."""
    from parsec_tpu.algos.potrf import build_potrf
    with pt.Context(nb_workers=1) as ctx:
        A = TwoDimBlockCyclic(32 * 8, 32 * 8, 8, 8, dtype=np.float32)
        A.register(ctx, "A")
        tp = build_potrf(ctx, A)
        t0 = time.perf_counter()
        report = verify_taskpool(tp)
        dt = time.perf_counter() - t0
    assert report.ok()
    assert dt < 30.0
