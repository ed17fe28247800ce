#!/usr/bin/env python
"""Bench-trajectory regression guard (`make bench-check`).

Compares the current BENCH_*.json set against the committed copies and
fails when a guarded metric regressed past its tolerance — the CI gate
that keeps the measurement ladder (BASELINE.md) monotone: dispatch p50,
stream overlap fraction, trace ring ratio and level-0 cost, collective
ratios, device stall reduction.

Baselines come from `git show <ref>:<file>` (default ref HEAD) or from
an explicit `--baseline-dir`.  Current values come from the working
tree (or `--current-dir`).

Oversubscription honesty: the bench suite records an `oversubscribed`
flag when the run timeshared more threads than cores (bench.py
host_provenance).  Timing-sensitive metrics from an oversubscribed run
(current OR baseline) are judged against `--oversub-slack` times the
tolerance — the number measures context-switch luck, so a tight gate
would flap — but they are still judged: a 3x regression fails even on a
1-core box.  Correctness metrics (bit-exactness flags) are never
relaxed.

Exit 0 = all guarded metrics within tolerance; 1 = regression; files
missing on either side are skipped with a note (a bench not yet run is
not a regression).
"""
import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (file, json.path, direction, rel_tol, timing_sensitive)
#   direction: "lower" = lower is better, "higher" = higher is better,
#              "equal" = must match exactly (correctness flags)
CHECKS = [
    ("BENCH_dispatch.json", "single_chain.p50_us", "lower", 0.15, True),
    ("BENCH_dispatch.json", "contended.p50_us", "lower", 0.20, True),
    ("BENCH_stream.json", "streamed.overlap_fraction", "higher", 0.35,
     True),
    ("BENCH_stream.json", "rails2_vs_rails1_throughput", "higher", 0.15,
     True),
    ("BENCH_trace.json", "ns_per_task.0", "lower", 0.05, True),
    ("BENCH_trace.json", "overhead_ns_per_task.level1", "lower", 0.50,
     True),
    ("BENCH_trace.json", "ring.vs_unbounded_level1", "lower", 0.10, True),
    # ptc-blackbox (PR 20): the crash-durable journal must stay
    # invisible to the level-0 dispatch hot path — the on/off ratio is
    # an oversubscription-slacked timing trajectory row, the <= 1.05
    # within_gate verdict an equal-direction flag, never relaxed
    ("BENCH_trace.json", "journal.overhead_ratio", "lower", 0.05, True),
    ("BENCH_trace.json", "journal.within_gate", "equal", 0.0, False),
    ("BENCH_collective.json", "coll_vs_chain_ratio", "lower", 0.25, True),
    ("BENCH_collective.json", "gemm_panel.overlap_fraction_gain",
     "higher", 0.50, True),
    ("BENCH_device.json", "wave_pipeline.hit_wave_stall_reduction",
     "higher", 0.15, True),
    ("BENCH_device.json", "out_of_core_gemm.correct", "equal", 0.0,
     False),
    # serving runtime (PR 9): hi-tenant p99 improvement over the no-QoS
    # control is timing (trajectory-guarded, oversubscription-slacked);
    # the in-document beats-control verdict and the continuous-vs-
    # sequential bit-exactness are correctness flags — never relaxed
    ("BENCH_serve.json", "hi_p99_improvement", "higher", 0.50, True),
    ("BENCH_serve.json", "qos.hi_p99_beats_control", "equal", 0.0,
     False),
    ("BENCH_serve.json", "decode.bit_identical", "equal", 0.0, False),
    # ptc-scope (PR 11): tenant SLO trajectory rows (timing,
    # oversubscription-slacked per convention) + the conformance
    # soundness verdict — full plan coverage and no pool beating its
    # makespan lower bound is CORRECTNESS, never relaxed
    ("BENCH_serve.json", "scope.ttft_p99_ms.hi", "lower", 0.50, True),
    ("BENCH_serve.json", "scope.tokens_per_s_p50.hi", "higher", 0.50,
     True),
    ("BENCH_serve.json", "scope.conformance.sound", "equal", 0.0, False),
    # ptc-share (PR 14): prefix-cache hit rate + warm tokens/s and the
    # k=4 speculative tokens/s are oversubscription-slacked timing
    # trajectory rows; warm-run and speculative bit-exactness vs the
    # cold / non-speculative baselines are equal-direction correctness
    # flags — never relaxed — as is the fewer-prefill-waves evidence
    # verdict
    ("BENCH_serve.json", "prefix.hit_rate", "higher", 0.50, True),
    ("BENCH_serve.json", "prefix.warm_tokens_per_s", "higher", 0.50,
     True),
    ("BENCH_serve.json", "prefix.bit_identical", "equal", 0.0, False),
    ("BENCH_serve.json", "prefix.fewer_prefill_than_cold", "equal", 0.0,
     False),
    ("BENCH_serve.json", "spec.k4.tokens_per_s", "higher", 0.50, True),
    ("BENCH_serve.json", "spec.bit_identical", "equal", 0.0, False),
    # ptc-route (PR 16): 2-replica fleet scaling and global prefix hit
    # rate are oversubscription-slacked timing trajectory rows (both
    # replicas timeshare one process's cores); the routed-vs-single
    # bit_identical verdict is an equal-direction correctness flag —
    # never relaxed
    ("BENCH_serve.json", "fleet.scaling", "higher", 0.50, True),
    ("BENCH_serve.json", "fleet.hit_rate", "higher", 0.50, True),
    ("BENCH_serve.json", "fleet.bit_identical", "equal", 0.0, False),
    # ptc-blackbox (PR 20): one FleetView federation refresh over both
    # replicas (tenant histogram merge + advertise) — timing row
    ("BENCH_serve.json", "fleet.fleet_scrape_ms", "lower", 0.50, True),
    # ptc-shard (PR 18): 2-/4-rank tensor-parallel decode vs the
    # single-rank reference — bit_identical (tokens AND exact f32
    # pre-logit bytes, prefix cache + speculative decoding live) is an
    # equal-direction correctness flag, never relaxed; the tp4-vs-tp1
    # per-token wall ratio is a timing trajectory row,
    # oversubscription-slacked (all ranks timeshare one host)
    ("BENCH_serve.json", "tp.bit_identical", "equal", 0.0, False),
    ("BENCH_serve.json", "tp.tp4_vs_tp1_ms_per_token", "lower", 0.50,
     True),
    # ptc-tune (PR 12): autotuned-vs-default ratios on the dispatch
    # chain and the 2-rank collective — timing trajectory rows,
    # oversubscription-slacked per convention; the beats_default
    # verdicts are equal-direction correctness flags, never relaxed
    ("BENCH_dispatch.json", "tuned.tuned_vs_default", "lower", 0.25,
     True),
    ("BENCH_dispatch.json", "tuned.beats_default", "equal", 0.0, False),
    ("BENCH_collective.json", "tuned.tuned_vs_default", "lower", 0.25,
     True),
    ("BENCH_collective.json", "tuned.beats_default", "equal", 0.0,
     False),
    ("BENCH_stream.json", "tuned.tuned_vs_default", "lower", 0.25,
     True),
    ("BENCH_stream.json", "tuned.beats_default", "equal", 0.0, False),
    # ptc-pilot (PR 19): the drift-soak recovery ratio is a timing
    # trajectory row (oversubscription-slacked), but the in-document
    # `recovered` verdict (>= 50% of incident-lost throughput clawed
    # back by the hot-swap, no restart) is an equal-direction
    # correctness flag — never relaxed — as are the adaptive-vs-fixed
    # spec_k verdict (deterministic wave/waste counts, not wall time)
    # and the every-k bit-identity of the token streams
    ("BENCH_control.json", "soak.recovery_ratio", "higher", 0.50, True),
    ("BENCH_control.json", "soak.recovered", "equal", 0.0, False),
    ("BENCH_control.json", "spec.adaptive_ge_best_fixed", "equal", 0.0,
     False),
    ("BENCH_control.json", "spec.bit_identical", "equal", 0.0, False),
    ("BENCH_control.json", "spec.adaptive_score", "higher", 0.25,
     False),
    # ptc-topo (PR 17): bit_identical and predicted_sound are
    # equal-direction correctness flags — the remapped run and the
    # hierarchical collectives must stay bit-exact and the plan's
    # per-class byte split must never under-bound the wire — never
    # relaxed.  dcn_reduction and the hier-vs-ring byte ratio are
    # deterministic byte-count trajectories (small control-plane
    # jitter only); the hier-vs-ring wall is a timing row,
    # oversubscription-slacked (4 ranks timeshare one host).
    ("BENCH_topo.json", "bit_identical", "equal", 0.0, False),
    ("BENCH_topo.json", "remap.predicted_sound", "equal", 0.0, False),
    ("BENCH_topo.json", "remap.dcn_reduction", "higher", 0.25, False),
    ("BENCH_topo.json", "allreduce.dcn_ratio_hier_vs_ring", "lower",
     0.25, False),
    ("BENCH_topo.json", "allreduce.hier_vs_ring", "lower", 0.50, True),
    # ptc-plan analyzer runtime on the potrf bench tiling (NT=16, 816
    # instances; PR 10): `make plan-graphs` emits the number, the 5 s
    # absolute budget lives in tools/plan_graphs.py — this row guards
    # the trajectory so the analyzer cannot quietly get 2x slower
    ("PLAN_graphs.json", "potrf_nt16_ms", "lower", 1.0, True),
]


def dig(obj, path):
    cur = obj
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def is_oversubscribed(doc) -> bool:
    """The recorded flag, wherever the suite put it (top level for the
    stream/trace/device/collective suites; per-section for dispatch)."""
    if not isinstance(doc, dict):
        return False
    if doc.get("oversubscribed"):
        return True
    for v in doc.values():
        if isinstance(v, dict) and v.get("oversubscribed"):
            return True
    return False


def load_current(fname, current_dir):
    path = os.path.join(current_dir, fname)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_baseline(fname, baseline_dir, ref):
    if baseline_dir:
        path = os.path.join(baseline_dir, fname)
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)
    try:
        out = subprocess.run(["git", "show", f"{ref}:{fname}"], cwd=REPO,
                             capture_output=True, check=True)
        return json.loads(out.stdout)
    except (subprocess.CalledProcessError, json.JSONDecodeError, OSError):
        return None


def check_all(current_dir, baseline_dir=None, ref="HEAD",
              oversub_slack=3.0):
    """Returns (rows, failures): rows are report dicts per metric."""
    cur_docs, base_docs = {}, {}
    rows, failures = [], 0
    for fname, path, direction, tol, timing in CHECKS:
        if fname not in cur_docs:
            cur_docs[fname] = load_current(fname, current_dir)
            base_docs[fname] = load_baseline(fname, baseline_dir, ref)
        cur_doc, base_doc = cur_docs[fname], base_docs[fname]
        row = {"file": fname, "metric": path, "direction": direction,
               "tol": tol}
        if cur_doc is None or base_doc is None:
            row["verdict"] = "skip"
            row["note"] = ("no current file" if cur_doc is None
                           else "no baseline")
            rows.append(row)
            continue
        cur, base = dig(cur_doc, path), dig(base_doc, path)
        row["current"], row["baseline"] = cur, base
        if cur is None or base is None:
            row["verdict"] = "skip"
            row["note"] = "metric missing"
            rows.append(row)
            continue
        if direction == "equal":
            ok = cur == base
            row["verdict"] = "ok" if ok else "FAIL"
            failures += 0 if ok else 1
            rows.append(row)
            continue
        eff_tol = tol
        oversub = is_oversubscribed(cur_doc) or is_oversubscribed(base_doc)
        if timing and oversub:
            eff_tol = tol * oversub_slack
            row["oversubscribed"] = True
            row["tol"] = eff_tol
        try:
            cur_f, base_f = float(cur), float(base)
        except (TypeError, ValueError):
            row["verdict"] = "skip"
            row["note"] = "non-numeric"
            rows.append(row)
            continue
        if base_f == 0:
            # regression direction still checkable against an absolute
            # epsilon of the tolerance itself
            delta = cur_f - base_f
            regressed = (delta > eff_tol if direction == "lower"
                         else delta < -eff_tol)
            row["delta"] = round(delta, 4)
        else:
            rel = (cur_f - base_f) / abs(base_f)
            regressed = (rel > eff_tol if direction == "lower"
                         else rel < -eff_tol)
            row["delta_rel"] = round(rel, 4)
        row["verdict"] = "FAIL" if regressed else "ok"
        failures += 1 if regressed else 0
        rows.append(row)
    return rows, failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--current-dir", default=REPO,
                    help="directory holding the fresh BENCH_*.json set")
    ap.add_argument("--baseline-dir", default=None,
                    help="directory of baseline copies (default: git)")
    ap.add_argument("--ref", default="HEAD",
                    help="git ref for baselines (default HEAD)")
    ap.add_argument("--oversub-slack", type=float, default=3.0,
                    help="tolerance multiplier for timing metrics from "
                         "oversubscribed runs (default 3.0)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON")
    args = ap.parse_args(argv)

    rows, failures = check_all(args.current_dir, args.baseline_dir,
                               args.ref, args.oversub_slack)
    if args.json:
        print(json.dumps({"failures": failures, "checks": rows},
                         indent=2))
    else:
        for r in rows:
            extra = ""
            if "delta_rel" in r:
                extra = f" ({r['delta_rel']:+.1%})"
            elif "delta" in r:
                extra = f" ({r['delta']:+g})"
            if r.get("oversubscribed"):
                extra += " [oversubscribed: slacked]"
            if r["verdict"] == "skip":
                print(f"skip  {r['file']}:{r['metric']} — {r['note']}")
            else:
                print(f"{r['verdict']:<5} {r['file']}:{r['metric']} "
                      f"{r.get('baseline')} -> {r.get('current')}{extra}")
        print(f"bench-check: {failures} regression(s)"
              if failures else "bench-check: ok")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
