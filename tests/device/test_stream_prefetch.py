"""Consumer half of the cross-rank streaming pipeline: event-driven
prefetch wakeup on remote delivery, and the streaming counters' export
through the device/unified stats surfaces.

The producer half (watermark serve, parked GETs, rails) lives in
tests/comm/test_stream.py; these tests pin the device-layer seams —
dp_deliver waking the prefetch lane instead of leaving it to poll, and
the writeback-lane slicer's evidence counters.
"""
import os

import numpy as np

from tests.comm import _workers
from tests.comm.test_multirank import _run_spmd


def test_remote_delivery_wakes_prefetch():
    """With the prefetch lane ON, every remote chunk delivery must wake
    it event-driven (prefetch_wakeups > 0) so h2d staging of a landed
    tile starts while the next one is still on the wire."""
    _run_spmd(_workers.stream_chain, 2, timeout=240.0, prefetch=True,
              expect_stream=True, check_wakeups=True)


def test_stream_serve_counters_exported():
    """The writeback-lane slicer's counters (stream_serves/slices/bytes/
    d2h_ns) surface through dev.stats AND the Context.device_stats()
    aggregation — asserted inside the worker, where both ranks serve."""
    _run_spmd(_workers.stream_chain, 2, timeout=240.0,
              expect_stream=True)


def test_prefetch_wake_event_exists_and_counts():
    """Local (single-process) contract: the device exposes the wake
    event, and setting it makes the idle lane's wait return — counted
    as a wakeup — without a remote delivery."""
    import parsec_tpu as pt
    from parsec_tpu.device import TpuDevice

    with pt.Context(nb_workers=1) as ctx:
        dev = TpuDevice(ctx, prefetch=True)
        try:
            assert hasattr(dev, "_pf_wake")
            before = dev.stats["prefetch_wakeups"]
            import time
            for _ in range(3):
                dev._pf_wake.set()
                time.sleep(0.01)
            deadline = time.time() + 5.0
            while time.time() < deadline and \
                    dev.stats["prefetch_wakeups"] <= before:
                dev._pf_wake.set()
                time.sleep(0.01)
            assert dev.stats["prefetch_wakeups"] > before, dev.stats
        finally:
            dev.stop()


def test_unified_stats_schema_single_rank():
    """Golden schema for the unified Context.stats() / metrics-registry
    namespaces (keys + types): exporter consumers get a stability
    contract.  Extended across PRs — PR 7 adds the `metrics` namespace
    and the registry's histogram/counter key sets."""
    import parsec_tpu as pt
    from parsec_tpu.device import TpuDevice

    with pt.Context(nb_workers=1) as ctx:
        dev = TpuDevice(ctx)
        try:
            s = ctx.stats()
            assert set(s) == {"sched", "device", "comm", "coll", "trace",
                              "metrics", "serve", "plan", "scope",
                              "control", "fleet"}
            # PR 20 (ptc-blackbox): fleet-federation namespace —
            # schema-stable with no FleetView attached
            assert s["fleet"] == {"enabled": False}
            # PR 11: request-scope namespace — schema-stable with no
            # registry attached, full rollup once one exists
            assert s["scope"] == {"enabled": False}
            # PR 19 (ptc-pilot): feedback-controller namespace —
            # schema-stable with no controller attached, live decision
            # ledger once one exists
            assert s["control"] == {"enabled": False}
            from parsec_tpu.analysis.control import Controller, SimClock
            ctrl = Controller(ctx, clock=SimClock())
            cst = ctx.stats()["control"]
            for k in ("enabled", "pools", "window", "window_n",
                      "drift_ratio", "drift_now", "retunes", "swaps",
                      "interrupts", "persisted", "pending", "target",
                      "decisions", "last_swap", "budget_shares",
                      "pressure", "spec_k"):
                assert k in cst, k
            assert cst["enabled"] is True
            ctrl.stop()
            assert ctx.stats()["control"] == {"enabled": False}
            reg_scope = ctx.scope_registry()
            sid = reg_scope.new_scope("t0")
            reg_scope.record_admitted(sid)
            reg_scope.record_done(sid)
            sc = ctx.stats()["scope"]
            assert set(sc) == {"enabled", "scopes", "requests", "live",
                               "tenants", "slo", "conformance"}
            assert sc["enabled"] is True and sc["requests"] == 1
            conf = sc["conformance"]
            # PR 19: `epochs` counts conformance-window rollovers (the
            # fold-only aggregates stay O(window), not O(run))
            assert set(conf) == {"pools", "planned", "epochs", "coverage",
                                 "makespan", "comm_bytes", "residency",
                                 "spills", "per_class"}
            for k in ("predicted_sum", "measured", "sound"):
                assert k in conf["comm_bytes"], k
            for k in ("level", "ring_bytes", "dropped_events", "clock"):
                assert k in s["trace"], k
            assert "bypass_hits" in s["sched"]
            assert "steals" in s["sched"]
            # PR 9: per-pool QoS rows + lane counters (serving runtime)
            for k in ("qos_selects", "qos_preempts",
                      "qos_preempt_enabled", "pools"):
                assert k in s["sched"], k
            assert isinstance(s["sched"]["pools"], list)
            # PR 9: serving namespace — schema-stable with no Server
            assert s["serve"] == {"enabled": False}
            for k in ("prefetch_hits", "spills", "stream_serves",
                      "prefetch_wakeups", "overlap_ratio", "devices",
                      "cache_peak_bytes"):
                assert k in s["device"], k
            # no wave compiler: batched launches are per-device
            # counters (`batches`, `batched_tasks` in each device's
            # stats), with no aggregate block of their own
            assert "fuse" not in s["device"]
            # PR 10: ptc-plan pre-run check namespace (device.plan_check)
            assert set(s["plan"]) == {"enabled", "checks", "over_budget",
                                      "predicted_spills",
                                      "last_peak_bytes",
                                      "last_budget_bytes"}
            assert isinstance(s["plan"]["enabled"], bool)
            comm = s["comm"]
            assert comm["enabled"] is False
            assert set(comm) == {"enabled", "engine", "rdv", "tuning",
                                 "stream", "topo"}
            # PR 17 (ptc-topo): per-link-class split — schema stable
            # with comm off: every class present and zeroed, flat
            # single-island matrix, source reported
            topo = comm["topo"]
            assert set(topo) == {"classes", "matrix", "n_islands",
                                 "source"}
            assert set(topo["classes"]) == {"loopback", "host", "ici",
                                            "dcn"}
            for row in topo["classes"].values():
                assert set(row) == {"bytes_sent", "bytes_recv",
                                    "msgs_sent", "msgs_recv",
                                    "parked_gets"}
                assert all(v == 0 for v in row.values())
            assert topo["n_islands"] >= 1
            for k in ("msgs_sent", "bytes_recv"):
                assert k in comm["engine"], k
            for k in ("gets_sent", "registered_bytes", "pending_pulls"):
                assert k in comm["rdv"], k
            for k in ("eager_limit", "chunk_size", "inflight", "stream"):
                assert k in comm["tuning"], k
            for k in ("sessions", "parked_gets", "overlap_ns", "d2h_ns",
                      "wire_ns", "reaps", "rails", "stream_enabled",
                      "overlap_fraction"):
                assert k in comm["stream"], k
            # PR 7: always-on metrics namespace (keys + types)
            met = s["metrics"]
            assert set(met) == {"enabled", "classes", "exporter_port",
                                "watchdog"}
            assert isinstance(met["enabled"], bool)
            assert isinstance(met["classes"], int)
            assert isinstance(met["exporter_port"], int)
            # None exactly when the env didn't arm it (the suite also
            # runs under PTC_MCA_runtime_watchdog as the
            # no-false-positive soak — the schema must hold there too)
            wd_armed = bool(os.environ.get("PTC_MCA_runtime_watchdog"))
            assert (met["watchdog"] is None) == (not wd_armed)
            # metrics-registry namespaces: histogram kinds fixed; the
            # flattened counter set covers every stats() leaf consumers
            # scrape (spot-pin the cross-namespace ones)
            reg = ctx.metrics_registry()
            snap = reg.snapshot()
            # PR 20 (ptc-blackbox): `scope_hists` carries the per-tenant
            # sparse histogram export FleetView federates across replicas
            assert set(snap) == {"t", "rank", "merged", "histograms",
                                 "counters", "scope_hists"}
            assert isinstance(snap["scope_hists"], dict)
            assert set(snap["histograms"]) == {
                "exec", "release", "h2d_stall", "comm_wait", "coll_wait"}
            counters = snap["counters"]
            for k in ("ptc_sched_bypass_hits", "ptc_coll_steps",
                      "ptc_trace_dropped_events", "ptc_comm_stream_reaps",
                      "ptc_device_overlap_ratio", "ptc_metrics_enabled"):
                assert k in counters, k
                assert isinstance(counters[k], (int, float)), k
            # every counter is JSON-serializable (the export's purpose)
            import json
            sd = dict(s)
            sd["device"] = {k: v for k, v in s["device"].items()
                            if k != "devices"}
            json.dumps(sd)
            json.dumps(snap)
            # a device result flows into the merged snapshot
            a = ctx.data(1, np.zeros(4, dtype=np.float32))
            assert a is not None
        finally:
            dev.stop()
