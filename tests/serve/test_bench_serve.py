"""BENCH_serve.json schema smoke: a miniature bench_serve_suite run
must produce every guarded field with the right types (the bench-check
rows and dashboard consumers rely on the shape, not the magnitudes)."""
import os
import sys

REPO = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, os.path.abspath(REPO))


def test_bench_serve_schema():
    import bench
    doc = bench.bench_serve_suite(
        n_hi=2, n_lo=3, max_new=2, workers=2, seq_check=1,
        n_pages=48, max_seqs=6, lo_prompt=(4, 8), hi_prompt=(3, 5),
        lo_new=2)
    assert doc["host"]["cpu_count"] >= 1
    assert "oversubscribed" in doc
    for side in ("qos", "control"):
        sec = doc[side]
        for tenant in ("hi", "lo"):
            for k in ("n", "p50_ms", "p99_ms", "mean_ms"):
                assert isinstance(sec[tenant][k], (int, float)), (side, k)
        assert sec["throughput_tok_s"] > 0
        assert sec["server_totals"]["completed"] == 5
    assert isinstance(doc["qos"]["hi_p99_beats_control"], bool)
    assert doc["hi_p99_improvement"] > 0
    adm = doc["admission"]
    assert adm["submitted"] == 12
    assert adm["rejected"] > 0          # backpressure really exercised
    assert adm["admitted"] + adm["rejected"] == adm["submitted"]
    assert doc["decode"]["bit_identical"] is True
    assert doc["decode"]["sequential_engine_checked"] == 1
    # the QoS run really rode the lanes
    assert doc["qos"]["qos_selects"] > 0
    # ptc-scope section (PR 11): tenant SLO quantiles + conformance
    sc = doc["scope"]
    for k in ("ttft_p99_ms", "ttft_p50_ms", "tokens_per_s_p50",
              "queue_wait_p99_ms"):
        assert set(sc[k]) == {"hi", "lo"}, (k, sc[k])
        assert sc[k]["hi"] >= 0
    conf = sc["conformance"]
    assert conf["coverage"] == 1.0, conf
    assert conf["sound"] is True, conf
    assert conf["per_class_classes"] > 0
    # ptc-share sections (PR 14): prefix cache + speculative decode
    pfx = doc["prefix"]
    assert 0.0 < pfx["hit_rate"] <= 1.0
    assert pfx["bit_identical"] is True
    assert pfx["fewer_prefill_than_cold"] is True
    assert pfx["pages_prefilled_warm"] < pfx["pages_prefilled_cold"]
    assert pfx["warm_tokens_per_s"] > 0
    sp = doc["spec"]
    assert sp["bit_identical"] is True
    assert sp["fewer_waves_than_off"] is True
    for k in ("off", "k2", "k4"):
        assert sp[k]["tokens_per_s"] > 0
    assert sp["k4"]["accept_rate"] == 1.0  # oracle self-draft
    vw = sp["verify_wave"]
    assert vw["single_batched_launch"] is True
    assert vw["batches"] > 0
    assert vw["device_launches"] < vw["batched_tasks"]
