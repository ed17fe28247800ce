#!/usr/bin/env python
"""Framework benchmark driver.  Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline (BASELINE.json): DPLASMA-style **spotrf GFLOP/s/chip**, run by the
native task runtime dispatching cached XLA executables on the TPU, in this
one process.  With no TPU it fails: there is no CPU fallback.  DPLASMA
practice: the matrix is generated in place (device-side, as dplrnt does) and
verified by residual; the timed section is the factorization itself
(dispatch + execution + intra-chip data movement), after a warmup pass that
populates the executable caches.

`vs_baseline`: the reference publishes no in-tree numbers (BASELINE.md).
The north star is >=70% of "A100+NVLink per-device spotrf"; we take
10 TFLOP/s as the A100 figure (TF32 dense Cholesky ballpark), so the
target is 7000 GFLOP/s/chip and vs_baseline = value / 7000.

`python bench.py --dispatch` reports the rung-1 metric instead
(task-dispatch p50 µs on an Ex04-style chain).
"""
import json
import sys
import time

import numpy as np

import parsec_tpu as pt


def host_provenance(threads=None):
    """ONE capture of host provenance + the oversubscription flag (the
    bench_dispatch_mt convention), shared by every bench document —
    bench-comm / bench-dispatch / bench-device / bench-stream each used
    to carry its own copy, which had already drifted three ways.
    `threads` (if given) is the number of runtime threads the measured
    configuration keeps busy; threads > cores flags the run as
    oversubscribed — the numbers then measure scheduling luck, not
    concurrency, and documents must say so.

    `host.fingerprint` is the stable host hash (cpu count, arch, page
    size, CPU feature flags) the ptc-tune store keys persisted knob
    winners by — one definition, shared with the tuner
    (parsec_tpu.analysis.tune.host_fingerprint)."""
    import os
    import platform

    from parsec_tpu.analysis.tune import host_fingerprint
    cpus = os.cpu_count() or 1
    doc = {"host": {"cpu_count": cpus, "platform": sys.platform,
                    "machine": platform.machine(),
                    "fingerprint": host_fingerprint()}}
    if threads is not None:
        doc["pipeline_threads"] = threads
        doc["oversubscribed"] = threads > cpus
    return doc


def _chain_taskpool(ctx, nb_tasks):
    """The Ex04-style single-RW-chain pool every dispatch bench (and
    the ptc-tune dispatch workload) measures."""
    ctx.register_arena("t", 8)
    tp = pt.Taskpool(ctx, globals={"NB": nb_tasks - 1})
    k = pt.L("k")
    tc = tp.task_class("Task")
    tc.param("k", 0, pt.G("NB"))
    tc.flow("A", "RW",
            pt.In(None, guard=(k == 0)),
            pt.In(pt.Ref("Task", k - 1, flow="A")),
            pt.Out(pt.Ref("Task", k + 1, flow="A"),
                   guard=(k < pt.G("NB"))),
            arena="t")
    tc.body_noop()
    return tp


def bench_dispatch_chain(nb_tasks: int = 20000, reps: int = 5):
    """Single-chain steady-state dispatch latency (measurement-ladder
    rung 1): p50/p99 of successor EXEC-begin deltas on an Ex04-style RW
    chain, 1 worker, span tracing on.  Returns the best rep's
    percentiles plus that run's Context.sched_stats() — the bypass/
    freelist counters are the evidence the fast path actually ran."""
    best = None
    for _ in range(reps):
        with pt.Context(nb_workers=1) as ctx:
            ctx.profile_enable(1)  # EXEC spans only: keep the hot path lean
            tp = _chain_taskpool(ctx, nb_tasks)
            tp.run()
            tp.wait()
            ev = ctx.profile_take()
            stats = ctx.sched_stats()
        begins = ev[(ev[:, 0] == 0) & (ev[:, 1] == 0)]
        order = np.argsort(begins[:, 3])   # sort by l0 = k
        t = begins[order, 7]               # t_ns (8-word event format)
        deltas_us = np.diff(t) / 1e3
        deltas_us = deltas_us[len(deltas_us) // 10:]
        rep = {"p50_us": round(float(np.percentile(deltas_us, 50)), 3),
               "p99_us": round(float(np.percentile(deltas_us, 99)), 3)}
        if best is None or rep["p50_us"] < best["p50_us"]:
            best = rep
            best["sched_stats"] = stats
    best.update(tasks=nb_tasks, reps=reps, workers=1)
    return best


def bench_profiling_overhead(nb_tasks: int = 20000, reps: int = 5):
    """Tracing cost per task (the reference's sp-perf standalone profiler
    benchmark role, tests/profiling-standalone/sp-perf.c): wall time of
    the 20k noop chain at trace level 0 (off), 1 (EXEC spans), and
    2 (+RELEASE spans +EDGE pairs)."""
    walls = {}
    for level in (0, 1, 2):
        best = None
        for _ in range(reps):
            with pt.Context(nb_workers=1) as ctx:
                if level:
                    ctx.profile_enable(level)
                ctx.register_arena("t", 8)
                tp = pt.Taskpool(ctx, globals={"NB": nb_tasks - 1})
                k = pt.L("k")
                tc = tp.task_class("Task")
                tc.param("k", 0, pt.G("NB"))
                tc.flow("A", "RW",
                        pt.In(None, guard=(k == 0)),
                        pt.In(pt.Ref("Task", k - 1, flow="A")),
                        pt.Out(pt.Ref("Task", k + 1, flow="A"),
                               guard=(k < pt.G("NB"))),
                        arena="t")
                tc.body_noop()
                t0 = time.perf_counter()
                tp.run()
                tp.wait()
                dt = time.perf_counter() - t0
            if best is None or dt < best:
                best = dt
        walls[level] = best
    per = {lv: walls[lv] / nb_tasks * 1e9 for lv in walls}
    return json.dumps({
        "metric": "profiling_overhead_ns_per_task",
        "value": round(per[1] - per[0], 1),
        "unit": "ns (level 1 spans vs off)",
        "vs_baseline": None,
        "config": {"tasks": nb_tasks,
                   "ns_per_task": {str(lv): round(per[lv], 1)
                                   for lv in per}},
    })


def bench_trace_suite(tasks: int = 20000, reps: int = 5,
                      ring_bytes: int = 1 << 16):
    """Tracing-cost ladder (make bench-trace -> BENCH_trace.json): wall
    cost per task of the noop chain at trace levels 0 (off), 1 (EXEC
    spans — the PR2 one-buffer-transaction-per-task contract), 2
    (+RELEASE spans +EDGE pairs), and level 1 under the flight-recorder
    RING (overwrite-oldest bounded buffers).  The ring push replaces the
    vector append with fixed-slot writes, so ring-vs-unbounded at level
    1 must stay within noise of 1.0 — that ratio is the acceptance
    number, recorded alongside the dropped-event count that proves the
    ring actually wrapped.

    The ALWAYS-ON METRICS cost rides along: level 0 is measured with the
    native histograms in their default (on) state AND force-disabled —
    their ratio is the PR 7 acceptance number (< 1.05: the noop dispatch
    path pays only the metrics_on branch + the sampled release tick;
    real bodies pay two ~10 ns clock reads, invisible at µs scale)."""
    def run(level, ring, metrics=True):
        best, dropped = None, 0
        for _ in range(reps):
            with pt.Context(nb_workers=1) as ctx:
                if level:
                    ctx.profile_enable(level)
                if ring:
                    ctx.profile_ring(ring)
                if not metrics:
                    ctx.metrics_enable(False)
                ctx.register_arena("t", 8)
                tp = pt.Taskpool(ctx, globals={"NB": tasks - 1})
                k = pt.L("k")
                tc = tp.task_class("Task")
                tc.param("k", 0, pt.G("NB"))
                tc.flow("A", "RW",
                        pt.In(None, guard=(k == 0)),
                        pt.In(pt.Ref("Task", k - 1, flow="A")),
                        pt.Out(pt.Ref("Task", k + 1, flow="A"),
                               guard=(k < pt.G("NB"))),
                        arena="t")
                tc.body_noop()
                t0 = time.perf_counter()
                tp.run()
                tp.wait()
                dt = time.perf_counter() - t0
                if ring:
                    dropped = max(dropped, ctx.profile_dropped())
            if best is None or dt < best:
                best = dt
        return best, dropped

    walls = {lv: run(lv, 0)[0] for lv in (0, 1, 2)}
    ring_wall, ring_dropped = run(1, ring_bytes)
    # the metrics on/off pair is measured BACK TO BACK (not reusing the
    # walls[0] run from a minute ago): the ratio is a ~4% effect, and
    # machine drift across the level-1/2/ring runs is the same order —
    # an adjacent pair keeps the comparison controlled
    met_on_wall = run(0, 0)[0]
    met_off_wall = run(0, 0, metrics=False)[0]

    # ptc-blackbox pair, also adjacent: the same level-0 chain with a
    # live Journal attached (cadence thread, crash handler armed,
    # fsync cadence ticking) vs without.  The recorder must be
    # invisible to the dispatch hot path (<= 1.05); the per-record
    # append cost of the buffered record() API rides along.
    import tempfile
    from parsec_tpu.profiling.blackbox import Journal

    def run_journal(enabled):
        best = None
        for _ in range(reps):
            with tempfile.TemporaryDirectory() as td, \
                    pt.Context(nb_workers=1) as ctx:
                jr = Journal(ctx, dirpath=td, fsync_s=0.2,
                             checkpoint_s=0.5) if enabled else None
                ctx.register_arena("t", 8)
                tp = pt.Taskpool(ctx, globals={"NB": tasks - 1})
                k = pt.L("k")
                tc = tp.task_class("Task")
                tc.param("k", 0, pt.G("NB"))
                tc.flow("A", "RW",
                        pt.In(None, guard=(k == 0)),
                        pt.In(pt.Ref("Task", k - 1, flow="A")),
                        pt.Out(pt.Ref("Task", k + 1, flow="A"),
                               guard=(k < pt.G("NB"))),
                        arena="t")
                tc.body_noop()
                t0 = time.perf_counter()
                tp.run()
                tp.wait()
                dt = time.perf_counter() - t0
                if jr is not None:
                    jr.stop()
            if best is None or dt < best:
                best = dt
        return best

    jr_on_wall = run_journal(True)
    jr_off_wall = run_journal(False)
    n_recs = 50000
    with tempfile.TemporaryDirectory() as td, \
            pt.Context(nb_workers=1) as ctx:
        jr = Journal(ctx, dirpath=td, fsync_s=0.2, checkpoint_s=1e9,
                     arm_crash=False)
        t0 = time.perf_counter()
        for i in range(n_recs):
            jr.record("serve", op="admit", tenant="bench", scope_id=i)
            if i % 8192 == 0:
                jr.flush(fsync=False)  # keep the pending list bounded
        rec_wall = time.perf_counter() - t0
        jr.stop()
    per = {lv: walls[lv] / tasks * 1e9 for lv in walls}
    ring_per = ring_wall / tasks * 1e9
    met_on_per = met_on_wall / tasks * 1e9
    met_off_per = met_off_wall / tasks * 1e9
    return {
        "schema": "bench-trace-v1",
        "knobs": {"tasks": tasks, "reps": reps, "ring_bytes": ring_bytes},
        "ns_per_task": {str(lv): round(per[lv], 1) for lv in per},
        "metrics": {
            # level 0 with the always-on histograms in their default
            # (on) state vs force-disabled (adjacent runs); the
            # overhead ratio is the PR 7 acceptance number (< 1.05)
            "ns_per_task_on": round(met_on_per, 1),
            "ns_per_task_off": round(met_off_per, 1),
            "overhead_ratio": (round(met_on_per / met_off_per, 3)
                               if met_off_per else None),
        },
        "journal": {
            # level-0 chain with a live recorder vs without (adjacent
            # pair); the acceptance gate is <= 1.05
            "ns_per_task_on": round(jr_on_wall / tasks * 1e9, 1),
            "ns_per_task_off": round(jr_off_wall / tasks * 1e9, 1),
            "overhead_ratio": (round(jr_on_wall / jr_off_wall, 3)
                               if jr_off_wall else None),
            "within_gate": bool(jr_off_wall
                                and jr_on_wall / jr_off_wall <= 1.05),
            # buffered record() append cost (format + list push; the
            # cadence thread owns the disk)
            "ns_per_record": round(rec_wall / n_recs * 1e9, 1),
        },
        "overhead_ns_per_task": {
            "level1": round(per[1] - per[0], 1),
            "level2": round(per[2] - per[0], 1),
            "ring_level1": round(ring_per - per[0], 1),
        },
        "ring": {
            "ns_per_task": round(ring_per, 1),
            "dropped_events": int(ring_dropped),
            # the acceptance ratio: ring mode vs the PR2 unbounded
            # level-1 cost (1.0 = identical; < 1.1 required)
            "vs_unbounded_level1": (round(ring_per / per[1], 3)
                                    if per[1] else None),
        },
        **host_provenance(threads=1),
    }


def bench_dispatch_mt(nb_tasks: int = 4000, lanes: int = 8, workers: int = 4,
                      reps: int = 5):
    """Multi-worker dispatch latency (VERDICT r3 weak #4: the single-
    worker chain p50 says nothing about release-path contention).
    `lanes` independent RW chains run concurrently on `workers` workers:
    every release_deps hits the dense dep engine while other workers do
    the same.  Reported: p50/p99 of intra-chain successor-begin deltas
    across all lanes — dispatch latency WITH contention.

    The output records os.cpu_count() and the EFFECTIVE worker count,
    and flags oversubscription explicitly: with workers > cores the
    workers timeshare one core, so the number measures context-switch
    luck, not lock contention (the r5 mt-dispatch caveat, now machine-
    readable instead of a footnote)."""
    best = None
    eff_workers = workers
    for _ in range(reps):
        with pt.Context(nb_workers=workers) as ctx:
            eff_workers = ctx.nb_workers
            ctx.profile_enable(1)
            ctx.register_arena("t", 8)
            tp = pt.Taskpool(ctx, globals={"NB": nb_tasks - 1,
                                           "L": lanes - 1})
            k, l = pt.L("k"), pt.L("l")
            tc = tp.task_class("Task")
            tc.param("l", 0, pt.G("L"))
            tc.param("k", 0, pt.G("NB"))
            tc.flow("A", "RW",
                    pt.In(None, guard=(k == 0)),
                    pt.In(pt.Ref("Task", l, k - 1, flow="A")),
                    pt.Out(pt.Ref("Task", l, k + 1, flow="A"),
                           guard=(k < pt.G("NB"))),
                    arena="t")
            tc.body_noop()
            tp.run()
            tp.wait()
            ev = ctx.profile_take()
            stats = ctx.sched_stats()
        begins = ev[(ev[:, 0] == 0) & (ev[:, 1] == 0)]
        deltas = []
        for lane in range(lanes):
            lane_ev = begins[begins[:, 3] == lane]  # l0 = l
            order = np.argsort(lane_ev[:, 4])       # l1 = k
            t = lane_ev[order, 7]
            d = np.diff(t) / 1e3
            deltas.append(d[len(d) // 10:])
        deltas = np.concatenate(deltas)
        rep = {"p50_us": round(float(np.percentile(deltas, 50)), 3),
               "p99_us": round(float(np.percentile(deltas, 99)), 3)}
        if best is None or rep["p50_us"] < best["p50_us"]:
            best = rep
            best["sched_stats"] = stats
    # oversubscription via the ONE shared capture (host_provenance),
    # not a local re-derivation; the flat cpu_count/oversubscribed keys
    # stay for schema compatibility
    prov = host_provenance(threads=eff_workers)
    over = prov["oversubscribed"]
    best.update(tasks=nb_tasks, lanes=lanes, reps=reps,
                workers_requested=workers, workers=eff_workers,
                cpu_count=prov["host"]["cpu_count"], oversubscribed=over)
    if over:
        best["caveat"] = (
            f"workers ({eff_workers}) > cores "
            f"({best['cpu_count']}): workers "
            "timeshare, so this measures scheduling luck, NOT lock "
            "contention — re-run on a multicore host for a real "
            "contended number")
        sys.stderr.write(f"bench-dispatch-mt WARNING: {best['caveat']}\n")
    return best


_LAST_POTRF_INFO = None  # per-run dispatch evidence (see _potrf_once)


def _potrf_once(N, nb, seed=0, check=False, profile=False,
                variant="panel"):
    """One spotrf run with device-resident data; returns (seconds,
    (residual, host check) or None).

    variant="panel" (default): build_potrf_panels — full-height N x nb
    panel tasks, each trailing update ONE MXU matmul, a wave one vmapped
    call.  variant="tile": the tiled dpotrf_L DAG (the distributed
    form), nb x nb tasks."""
    import os
    from parsec_tpu.algos import build_potrf, build_potrf_panels
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device.bench_utils import (generate_spd_on_device,
                                               generate_spd_panels_on_device,
                                               potrf_host_check,
                                               potrf_residual, spotrf_device,
                                               wait_device_tiles)
    workers = int(os.environ.get("PTC_BENCH_WORKERS", "4"))
    with pt.Context(nb_workers=workers) as ctx:
        if variant == "panel":
            A = TwoDimBlockCyclic(N, N, N, nb, dtype=np.float32)
        else:
            A = TwoDimBlockCyclic(N, N, nb, nb, dtype=np.float32)
        A.register(ctx, "A")
        dev = spotrf_device(ctx, A)
        t_g0 = time.perf_counter()
        if variant == "panel":
            a_stacked = generate_spd_panels_on_device(dev, A, seed=seed)
        else:
            a_stacked = generate_spd_on_device(dev, A, seed=seed)
        a_stacked.block_until_ready()
        del a_stacked  # the device cache holds the tiles
        t_g1 = time.perf_counter()
        if variant == "panel":
            tp = build_potrf_panels(ctx, A, dev=dev)
        else:
            tp = build_potrf(ctx, A, dev=dev)
        t0 = time.perf_counter()
        tp.run()
        tp.wait()
        t_w = time.perf_counter()
        # all tasks enqueued; done when every tile's device value lands
        wait_device_tiles(dev, A)
        dt = time.perf_counter() - t0
        # per-run evidence for the JSON line: device-call count +
        # dispatch counters + wall breakdown
        sd = dev.stats
        singles = sd["tasks"] - sd.get("batched_tasks", 0) \
            - sd.get("spec_hits", 0)
        global _LAST_POTRF_INFO
        _LAST_POTRF_INFO = {
            "device_calls": sd.get("batches", 0) + max(0, singles),
            "counters": {k: sd.get(k, 0) for k in
                         ("tasks", "batches", "batched_tasks",
                          "fused_flows", "eager_gathers", "h2d_bytes",
                          "d2h_bytes", "wb_tasks", "spec_hits",
                          "spec_store", "spec_misses", "batch_fallbacks")},
            "wall": {"gen_s": round(t_g1 - t_g0, 3),
                     "enqueue_s": round(t_w - t0, 3),
                     "total_s": round(dt, 3)},
        }
        if profile:
            s = dev.stats
            sys.stderr.write(
                f"[profile] N={N} nb={nb} gen={t_g1 - t_g0:.2f}s "
                f"enqueue={t_w - t0:.2f}s total={dt:.2f}s "
                f"tasks={s['tasks']} batches={s.get('batches', 0)} "
                f"batched={s.get('batched_tasks', 0)} "
                f"fused={s.get('fused_flows', 0)} "
                f"eager={s.get('eager_gathers', 0)} "
                f"h2d={s['h2d_bytes']} d2h={s['d2h_bytes']} "
                f"wb={s.get('wb_tasks', 0)} "
                f"spec={s.get('spec_hits', 0)}/"
                f"{s.get('spec_store', 0)}\n")
        resid = None
        if check:
            resid = (potrf_residual([(dev, A)], seed),
                     potrf_host_check([(dev, A)], seed))
        dev.stop()
    # the context/device just left scope: collect NOW so the next rep's
    # allocations don't race the old rep's uncollected device arrays
    # (ctypes-callback cycles keep them alive past the with-block)
    import gc
    gc.collect()
    return dt, resid


def _chip_info():
    """(device_kind, measured fp32 matmul GFLOP/s at the default
    precision) of the chip the bench runs on.  The matmul rate is
    measured, not tabulated, so spotrf numbers read relative to what
    *this* chip's MXU does on plain fp32 GEMM."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "cpu")
    n = 4096
    a = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda x: x @ x)
    f(a).block_until_ready()  # compile + settle
    reps = 8
    t0 = time.perf_counter()
    x = a
    for _ in range(reps):
        x = f(x)
    x.block_until_ready()
    dt = time.perf_counter() - t0
    return kind, reps * 2 * n ** 3 / dt / 1e9


def bench_spotrf(N=16384, nb=1024, reps=2, variant="panel"):
    import os
    from parsec_tpu.algos import potrf_flops
    from parsec_tpu.device.bench_utils import residual_bound
    profile = bool(os.environ.get("PTC_BENCH_PROFILE"))
    # warmup: compiles the kernels + generator + small graph;
    # 16*nb gives nt=16 so the batched buckets up to 16 pre-compile too.
    # Never warm up BIGGER than the measured run (the N=4096 rung would
    # otherwise pay an N=8192 warmup - slower than the rung itself).
    # Panel kernels recompile at the full height anyway (panels are
    # N-tall), so a big panel warmup is wasted chip time: warm tiny —
    # just the runtime/import/device paths; rep 1 carries the real
    # compiles and rep 2 measures clean.
    warm_n = min((4 if variant == "panel" else 16) * nb, N)
    _potrf_once(warm_n, nb, seed=1, variant=variant)
    best = None
    resid = None
    for rep in range(reps):
        dt, r = _potrf_once(N, nb, seed=0, check=(rep == 0),
                            profile=profile, variant=variant)
        if rep == 0:
            resid = r
        if best is None or dt < best:
            best = dt
    bound = residual_bound(N)
    if not max(resid) <= bound:
        raise RuntimeError(f"spotrf residual check failed: (residual, "
                           f"host check) = {resid} > {bound}")
    return potrf_flops(N) / best / 1e9


def bench_ep(nb_tasks=100000, workers=(1, 2, 4, 8), scheds=None):
    """Embarrassingly-parallel scheduler throughput (reference vehicle:
    tests/runtime/scheduling/ep.jdf — the benchmark every scheduler is
    judged by).  Native noop bodies: no GIL, pure dispatch path.  Prints
    a (scheduler x workers) tasks/s table to stderr and returns the
    matrix."""
    if scheds is None:
        scheds = ["lfq", "lws", "ll", "ltq", "pbq", "lhq", "gd", "ap",
                  "spq", "ip", "rnd"]
    results = {}
    steals = {}
    for w in workers:
        for s in scheds:
            with pt.Context(nb_workers=w, scheduler=s) as ctx:
                tp = pt.Taskpool(ctx, globals={"NB": nb_tasks - 1})
                tc = tp.task_class("EP")
                tc.param("k", 0, pt.G("NB"))
                tc.body_noop()
                t0 = time.perf_counter()
                tp.run()
                tp.wait()
                dt = time.perf_counter() - t0
                stl = sum(ctx.worker_steals())
            results[(s, w)] = nb_tasks / dt
            steals[(s, w)] = stl
    sys.stderr.write("ep tasks/s (%d tasks; (steals) per cell)\n%-6s"
                     % (nb_tasks, "sched"))
    for w in workers:
        sys.stderr.write(f"{w:>12d}w")
    sys.stderr.write("\n")
    for s in scheds:
        sys.stderr.write("%-6s" % s)
        for w in workers:
            sys.stderr.write(
                f"{results[(s, w)]:>13,.0f}({steals[(s, w)]})")
        sys.stderr.write("\n")
    return results


def bench_ring(S=8, T=2048, d=128, reps=3):
    """Runtime-vs-GSPMD perf point for ONE ML algorithm on the real chip
    (VERDICT r3 #9): the same blockwise attention computed (a) as a
    native-runtime taskpool dispatching cached executables per block pair
    via the TPU device module, and (b) as one jitted XLA call (what the
    GSPMD library path compiles to on a single chip — parallel/
    ring_attention.py's per-device program).  The ratio is the honest
    task-runtime overhead number for this shape."""
    import os

    import jax
    from parsec_tpu.algos.ring_attention import run_ring_attention
    from parsec_tpu.device import TpuDevice

    rng = np.random.default_rng(0)
    L = S * T
    q = (rng.standard_normal((L, d)) / 8).astype(np.float32)
    k = (rng.standard_normal((L, d)) / 8).astype(np.float32)
    v = (rng.standard_normal((L, d)) / 8).astype(np.float32)

    # Both paths timed HOST-TO-HOST per rep — fresh placement of the
    # numpy inputs, compute, dense host readback — so the transfer cost
    # lands on both sides of the ratio.
    # (b) one fused XLA call
    def full_att(qj, kj, vj):
        s = (qj @ kj.T) * (d ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        return p @ vj

    f = jax.jit(full_att)
    o_ref = np.asarray(f(q, k, v))  # compile + settle
    gspmd_s = None
    for _ in range(reps):
        t0 = time.perf_counter()
        qj, kj, vj = (jax.device_put(x) for x in (q, k, v))
        o_host = np.asarray(f(qj, kj, vj))
        dt = time.perf_counter() - t0
        if gspmd_s is None or dt < gspmd_s:
            gspmd_s = dt
    del o_host

    # (a) the same work through the native runtime + device module.
    # On the real chip, accumulate the ATT wave into one vmapped call
    # (the spotrf bench's setting).  On CPU the window only ADDS latency
    # (dispatch is ns), so smoke runs leave it off.
    if jax.devices()[0].platform != "cpu":
        os.environ.setdefault("PTC_DEVICE_BATCH_WAIT_MS", "5")
    runtime_best = None
    out = None
    for rep in range(reps + 1):  # first run pays compiles: warmup
        with pt.Context(nb_workers=2) as ctx:
            dev = TpuDevice(ctx)
            t0 = time.perf_counter()
            Oc = run_ring_attention(ctx, S, T, d, q, k, v, dev=dev)
            got = Oc.to_dense()
            dt = time.perf_counter() - t0
            if rep > 0 and (runtime_best is None or dt < runtime_best):
                runtime_best = dt
            if out is None:
                out = got
            dev.stop()
    err = float(np.abs(out - o_ref).max())
    if not np.isfinite(err) or err > 5e-2:
        raise RuntimeError(f"ring attention mismatch vs XLA oracle: {err}")
    if jax.devices()[0].platform == "cpu":
        chip = "cpu"  # smoke runs: skip the matmul peak probe
    else:
        chip, _ = _chip_info()
    return json.dumps({
        "metric": "ring_attention_runtime_over_gspmd",
        "value": round(runtime_best / gspmd_s, 3),
        "unit": "x (lower is better, 1.0 = parity)",
        "vs_baseline": round(gspmd_s / runtime_best, 3),
        "config": {"S": S, "T": T, "d": d, "seq": L},
        "chip_kind": chip,
        "gspmd_ms": round(gspmd_s * 1e3, 2),
        "runtime_ms": round(runtime_best * 1e3, 2),
        "max_abs_err": err,
    })


def _ep_json():
    res = bench_ep()
    best = max(res, key=res.get)
    return json.dumps({
        "metric": "ep_tasks_per_sec",
        "value": round(res[best], 0),
        "unit": "tasks/s",
        "vs_baseline": round(res[best] / 1e6, 3),  # 1M tasks/s target
        "config": {"sched": best[0], "workers": best[1]},
    })


def _dispatch_json(single=None):
    if single is None:
        single = bench_dispatch_chain()
    p50_us = single["p50_us"]
    return json.dumps({
        "metric": "task_dispatch_p50",
        "value": round(p50_us, 3),
        "unit": "us",
        "vs_baseline": round(5.0 / p50_us, 3),
    })


def bench_dispatch_tuned(tasks=20000, reps=3, topk=3):
    """Plan-driven autotuning of the dispatch chain (ptc-tune,
    ROADMAP item 5): warm a chain run so the always-on histograms seed
    the CostModel, let the schedule simulator propose knob vectors
    (the magazine batch is the live axis on a comm-free single-rank
    chain), validate the top-k + the hand-tuned defaults with REAL
    chain runs under apply_knobs (fresh contexts, so the env-read
    native knobs bind), and persist the winner keyed by (graph
    signature, host fingerprint).  The recorded ratio
    tuned_vs_default (<= 1.0 = the autotuner beat or matched the
    defaults) is a bench_check trajectory row; beats_default is the
    equal-direction flag."""
    from parsec_tpu.analysis import CostModel, autotune
    from parsec_tpu.analysis.tune import apply_knobs
    from parsec_tpu.profiling import take_trace

    def measure(knobs):
        """Best-of-reps chain wall time under the vector; the last rep
        carries a level-2 trace so the validator records the
        compare_critpath predicted-vs-measured ratio per run."""
        best, trace = None, None
        with apply_knobs(knobs):
            for rep in range(reps + 1):  # rep 0 = untimed warmup (the
                with pt.Context(nb_workers=1) as ctx:  # first candidate
                    ctx.profile_enable(2)  # must not pay cold buffers)
                    tp = _chain_taskpool(ctx, tasks)
                    t0 = time.perf_counter()
                    tp.run()
                    tp.wait()
                    dt = time.perf_counter() - t0
                    tr = take_trace(ctx)
                if rep == 0:
                    continue
                if best is None or dt < best:
                    best, trace = dt, tr
        return best, trace

    with pt.Context(nb_workers=1) as ctx:
        warm = _chain_taskpool(ctx, tasks)
        warm.run()
        warm.wait()
        cost = CostModel.from_context(ctx)
        res = autotune(warm, measure=measure, topk=topk, cost=cost,
                       workers=1)
    # the default vector always rides along (propose() guarantees it);
    # find it by knob equality
    from parsec_tpu.analysis.tune import default_knobs
    dk = default_knobs()
    default = next(r for r in res["validated"] if r["knobs"] == dk)
    winner = res["winner"]
    ratio = (winner["measured_s"] / default["measured_s"]
             if default["measured_s"] else None)
    return {
        "workload": "single_chain", "tasks": tasks, "reps": reps,
        "signature": res["signature"], "host": res["host"],
        "default_knobs": dk,
        "default_wall_s": round(default["measured_s"], 6),
        "default_us_per_task": round(
            default["measured_s"] / tasks * 1e6, 4),
        "winner_knobs": winner["knobs"],
        "winner_wall_s": round(winner["measured_s"], 6),
        "winner_us_per_task": round(
            winner["measured_s"] / tasks * 1e6, 4),
        "tuned_vs_default": round(ratio, 4) if ratio else None,
        "beats_default": bool(ratio is not None and ratio <= 1.0),
        "critpath_ratio": winner.get("critpath_ratio"),
        "validated": [
            {"knobs": r["knobs"],
             "predicted_ns": round(r["predicted_ns"]),
             "measured_s": round(r["measured_s"], 6),
             "predicted_vs_wall": r.get("predicted_vs_wall"),
             "critpath_ratio": r.get("critpath_ratio")}
            for r in res["validated"]],
        "persisted": res["persisted"],
    }


def bench_dispatch_suite(tasks=20000, mt_tasks=4000, reps=5, workers=4,
                         lanes=8):
    """The `make bench-dispatch` document (BENCH_dispatch.json):
    single-chain AND contended dispatch percentiles, each carrying the
    sched_stats counters that prove which fast paths fired, plus host
    provenance so a 1-core contended number can't masquerade as a
    contention measurement, plus the ptc-tune autotuned-vs-default
    section (ROADMAP item 5 evidence)."""
    from parsec_tpu.utils import params as _mca
    single = bench_dispatch_chain(tasks, reps)
    contended = bench_dispatch_mt(mt_tasks, lanes, workers, reps)
    tuned = bench_dispatch_tuned(tasks, reps=max(2, reps - 2))
    return {
        "bench": "dispatch",
        **host_provenance(),
        "sched": _mca.get("runtime.sched"),
        "sched_bypass": bool(_mca.get("sched.bypass")),
        "budget_us": 5.0,
        "single_chain": single,
        "contended": contended,
        "tuned": tuned,
    }


def _pair_spans(ev, key, aux_filter=None):
    """(t0, t1, l0, end_aux, begin_aux) tuples from consecutive
    begin/end events of one trace key.  DEVICE and H2D spans are
    emitted by single threads (manager / prefetch lane), so
    time-ordered pairing is exact.  DEVICE begin aux carries the
    ptc-fuse mark (0 plain, n >= 1 = a certified wave executable
    covering n waves)."""
    rows = ev[ev[:, 0] == key]
    if aux_filter is not None:
        rows = rows[rows[:, 6] == aux_filter]
    rows = rows[np.argsort(rows[:, 7], kind="stable")]
    spans, open_t = [], None
    for r in rows:
        if r[1] == 0:
            open_t = (r[7], r[3], r[6])
        elif open_t is not None:
            spans.append((open_t[0], r[7], open_t[1], r[6], open_t[2]))
            open_t = None
    return spans


def _overlap_fraction(h2d_spans, exec_spans):
    """Fraction of h2d span time covered by device-dispatch spans —
    the trace-level transfer/compute overlap evidence."""
    total = sum(s[1] - s[0] for s in h2d_spans)
    if total <= 0:
        return None
    merged = []
    for t0, t1, *_ in sorted(exec_spans):
        if merged and t0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t1)
        else:
            merged.append([t0, t1])
    cov = 0
    for t0, t1, *_ in h2d_spans:
        for m0, m1 in merged:
            lo, hi = max(t0, m0), min(t1, m1)
            if lo < hi:
                cov += hi - lo
    return cov / total


def _device_wave_run(prefetch, tiles, elems, batch, workers=2):
    """One wave-pipeline run: `tiles` independent device tasks, each
    staging a distinct Mem tile, batch_max=batch so the job executes as
    ~tiles/batch waves.  Returns (wave spans, device_stats, wall_s)."""
    from parsec_tpu.device import TpuDevice
    from parsec_tpu.profiling.trace import KEY_DEVICE, KEY_H2D
    tb = elems * 4
    rng = np.random.default_rng(11)
    src = rng.standard_normal((tiles, elems)).astype(np.float32)
    dst = np.zeros((tiles, elems), dtype=np.float32)
    with pt.Context(nb_workers=workers) as ctx:
        ctx.profile_enable(1)
        ctx.register_linear_collection("T", src, elem_size=tb)
        ctx.register_linear_collection("O", dst, elem_size=tb)
        ctx.register_arena("t", tb)
        dev = TpuDevice(ctx, autostart=False, prefetch=prefetch)
        dev.batch_max = batch
        dev.start()
        tp = pt.Taskpool(ctx, globals={"NT": tiles - 1})
        k = pt.L("k")
        tc = tp.task_class("Wave")
        tc.param("k", 0, pt.G("NT"))
        tc.flow("X", "R", pt.In(pt.Mem("T", k)), arena="t")
        tc.flow("Y", "RW", pt.In(pt.Mem("O", k)), pt.Out(pt.Mem("O", k)),
                arena="t")
        dev.attach(tc, tp, kernel=lambda x, y: x * 2.0 + y,
                   reads=["X", "Y"], writes=["Y"],
                   shapes={"X": (elems,), "Y": (elems,)},
                   dtype=np.float32)
        t0 = time.perf_counter()
        tp.run()
        tp.wait()
        dev.flush()
        wall = time.perf_counter() - t0
        ev = ctx.profile_take()
        stats = ctx.device_stats()
        dev.stop()
    waves = _pair_spans(ev, KEY_DEVICE)
    h2d_pf = _pair_spans(ev, KEY_H2D, aux_filter=1)
    stats.pop("devices", None)
    stats["trace_overlap_fraction"] = _overlap_fraction(h2d_pf, waves)
    return waves, stats, wall


def bench_device_pipeline(tiles=96, elems=32 * 1024, batch=8, reps=3):
    """Staged-vs-prefetched wave dispatch (the `make bench-device`
    headline): the same wave workload runs with the prefetch lane OFF
    (staged baseline — every wave pays its h2d synchronously at
    dispatch) and ON.  Per-wave dispatch-time h2d stall comes straight
    off the DEVICE span's end-aux (0 == prefetch-hit wave); the overlap
    fraction pairs prefetch H2D spans against dispatch spans."""

    def summarize(waves, stats, wall):
        stalls = np.array([w[3] for w in waves], dtype=np.float64)
        lat = np.array([w[1] - w[0] for w in waves], dtype=np.float64)
        hit = stalls == 0
        return {
            "waves": len(waves),
            "wall_s": round(wall, 4),
            "wave_p50_us": round(float(np.percentile(lat, 50)) / 1e3, 2)
            if len(lat) else None,
            "stall_per_wave_us": round(float(stalls.mean()) / 1e3, 2)
            if len(stalls) else None,
            "stall_total_ms": round(float(stalls.sum()) / 1e6, 3),
            "prefetch_hit_waves": int(hit.sum()),
            "staged_waves": int((~hit).sum()),
            "hit_wave_stall_us": round(float(stalls[hit].mean()) / 1e3, 3)
            if hit.any() else None,
            "staged_wave_stall_us":
                round(float(stalls[~hit].mean()) / 1e3, 2)
                if (~hit).any() else None,
            "device_stats": stats,
        }

    best_off = best_on = None
    for _ in range(reps):
        off = summarize(*_device_wave_run(False, tiles, elems, batch))
        on = summarize(*_device_wave_run(True, tiles, elems, batch))
        if best_off is None or off["stall_per_wave_us"] < \
                best_off["stall_per_wave_us"]:
            best_off = off
        if best_on is None or on["stall_total_ms"] < \
                best_on["stall_total_ms"]:
            best_on = on
    off_stall = best_off["stall_per_wave_us"] or 0.0
    hit_stall = best_on["hit_wave_stall_us"]
    reduction = None
    if off_stall > 0 and hit_stall is not None:
        reduction = round(1.0 - hit_stall / off_stall, 4)
    return {
        "tiles": tiles, "tile_bytes": elems * 4, "batch": batch,
        "reps": reps,
        "staged": best_off,
        "prefetched": best_on,
        # the acceptance metric: dispatch-time h2d stall of prefetch-hit
        # waves vs the staged baseline's per-wave stall (target >= 0.8)
        "hit_wave_stall_reduction": reduction,
        "total_stall_reduction": round(
            1.0 - best_on["stall_total_ms"] /
            max(best_off["stall_total_ms"], 1e-9), 4),
    }


def bench_device_ooc_gemm(m=512, n=512, k=64, mb=32):
    """Out-of-core leg: a GEMM whose tile set is 2x the device byte
    budget (C alone exceeds it, so clean eviction cannot save the run —
    dirty mirrors MUST spill through the writeback lane).  Evidence:
    completion, exact result vs the numpy reference, nonzero spill
    counters, residency back under budget at the end."""
    from parsec_tpu.algos import build_gemm
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device import TpuDevice
    rng = np.random.default_rng(3)
    with pt.Context(nb_workers=2) as ctx:
        A = TwoDimBlockCyclic(m, k, mb, mb, dtype=np.float32)
        B = TwoDimBlockCyclic(k, n, mb, mb, dtype=np.float32)
        Cc = TwoDimBlockCyclic(m, n, mb, mb, dtype=np.float32)
        A.from_dense(rng.standard_normal((m, k), dtype=np.float32))
        B.from_dense(rng.standard_normal((k, n), dtype=np.float32))
        Cc.from_dense(np.zeros((m, n), np.float32))
        A.register(ctx, "A")
        B.register(ctx, "B")
        Cc.register(ctx, "C")
        tile_set = (m * k + k * n + m * n) * 4
        budget = tile_set // 2
        dev = TpuDevice(ctx, cache_bytes=budget)
        tp = build_gemm(ctx, A, B, Cc, dev=dev)
        t0 = time.perf_counter()
        tp.run()
        tp.wait()
        dev.flush()
        wall = time.perf_counter() - t0
        stats = ctx.device_stats()
        used = dev._cache_used
        dev.stop()
        ref = A.to_dense() @ B.to_dense()
        err = float(np.abs(Cc.to_dense() - ref).max())
        correct = bool(np.allclose(Cc.to_dense(), ref, rtol=1e-3,
                                   atol=1e-3))
    stats.pop("devices", None)
    return {
        "m": m, "n": n, "k": k, "mb": mb,
        "tile_set_bytes": tile_set, "budget_bytes": budget,
        "budget_ratio": round(tile_set / budget, 2),
        "wall_s": round(wall, 3),
        "correct": correct, "max_abs_err": err,
        "spills": stats["spills"], "spill_bytes": stats["spill_bytes"],
        "reserve_fails": stats["reserve_fails"],
        "ooc_waits": stats["ooc_waits"],
        "end_residency_bytes": int(used),
        "device_stats": stats,
    }


def _fuse_gemm_run(fuse, m, k, nb, batch_wait_ms=2.0):
    """One wave-fusion GEMM run: single-rank owner-computes k-chain
    (kt = k/nb waves of (m/nb)^2 Gemm tasks).  Returns (C dense,
    DEVICE launch count, fused-marked launch count, fuse counters,
    wall_s)."""
    from parsec_tpu.algos import build_gemm
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device import TpuDevice
    from parsec_tpu.profiling.trace import KEY_DEVICE
    from parsec_tpu.utils import params as _mca
    _mca.set("device.wave_fuse", bool(fuse))
    try:
        rng = np.random.default_rng(5)
        with pt.Context(nb_workers=2) as ctx:
            A = TwoDimBlockCyclic(m, k, nb, nb, dtype=np.float32)
            B = TwoDimBlockCyclic(k, m, nb, nb, dtype=np.float32)
            Cc = TwoDimBlockCyclic(m, m, nb, nb, dtype=np.float32)
            A.from_dense(rng.standard_normal((m, k), dtype=np.float32))
            B.from_dense(rng.standard_normal((k, m), dtype=np.float32))
            Cc.from_dense(np.zeros((m, m), np.float32))
            A.register(ctx, "A")
            B.register(ctx, "B")
            Cc.register(ctx, "C")
            ctx.profile_enable(1)
            dev = TpuDevice(ctx)
            # coalesce whole waves per pop (the spotrf bench setting):
            # launch economics, not pop-timing luck, is under test
            dev.batch_wait_ms = batch_wait_ms
            tp = build_gemm(ctx, A, B, Cc, dev=dev)
            t0 = time.perf_counter()
            tp.run()
            tp.wait()
            dev.flush()
            wall = time.perf_counter() - t0
            ev = ctx.profile_take()
            stats = ctx.device_stats()
            dev.stop()
            out = Cc.to_dense().copy()
        spans = _pair_spans(ev, KEY_DEVICE)
        fused_marked = sum(1 for s in spans if s[4] > 0)
        return out, len(spans), fused_marked, stats["fuse"], wall
    finally:
        _mca.unset("device.wave_fuse")


def bench_device_fuse_gemm(m=128, k=512, nb=32, reps=3):
    """Wave mega-kernelization section (`make bench-device`): the SAME
    deep-k GEMM runs with the wave compiler ON (certified waves +
    chains compile into one cached executable each; downstream waves
    complete from parked results with zero launches) and OFF
    (PTC_MCA_device_wave_fuse=0 — the PR 12 per-group batched path).
    Launch counts come straight off paired DEVICE spans; acceptance is
    >= 5x fewer launches at BIT-EXACT results (the equal-direction
    gate bench_check never relaxes)."""
    tasks = (m // nb) ** 2 * (k // nb)
    best_f = best_u = None
    bit_identical = True
    fuse_stats = None
    for _ in range(reps):
        cf, lf, marked, fs, wf = _fuse_gemm_run(True, m, k, nb)
        cu, lu, _, _, wu = _fuse_gemm_run(False, m, k, nb)
        bit_identical = bit_identical and \
            (cf.tobytes() == cu.tobytes())
        # fewest launches first, then wall (rep 0 pays the one-time
        # chain-program compile; the cache makes later reps steady-state)
        if best_f is None or (lf, wf) < (best_f[0], best_f[2]):
            best_f = (lf, marked, wf)
            fuse_stats = fs
        if best_u is None or (lu, wu) < best_u:
            best_u = (lu, wu)
    launches_f, marked, wall_f = best_f
    launches_u, wall_u = best_u
    return {
        "m": m, "k": k, "nb": nb, "reps": reps,
        "tasks": tasks,
        "waves": k // nb,
        "launches_fused": launches_f,
        "launches_unfused": launches_u,
        "fused_marked_launches": marked,
        # the two bench_check trajectory rows + the correctness gate
        "launches_per_task": round(launches_f / tasks, 5),
        "fused_vs_unfused_ratio": round(launches_u
                                        / max(1, launches_f), 2),
        "bit_identical": bit_identical,
        "wall_fused_s": round(wall_f, 4),
        "wall_unfused_s": round(wall_u, 4),
        "fuse_stats": {kk: vv for kk, vv in (fuse_stats or {}).items()},
    }


def bench_device_suite(tiles=96, elems=32 * 1024, batch=8, reps=3,
                       gemm_m=512, gemm_k=64, gemm_mb=32):
    """The `make bench-device` document (BENCH_device.json): staged-vs-
    prefetched wave latency + overlap evidence, the 2x-budget
    out-of-core GEMM, and host provenance (the pipeline threads —
    workers + manager + writeback + prefetch — timeshare on small
    hosts, which is flagged, not silently reported)."""
    from parsec_tpu.utils import params as _mca
    workers = 2
    threads = workers + 3  # manager + writeback + prefetch lanes
    doc = {
        "bench": "device",
        **host_provenance(threads=threads),
        "knobs": {
            "prefetch_depth": _mca.get("device.prefetch_depth"),
            "staging_slots": _mca.get("device.staging_slots"),
            "out_of_core": _mca.get("device.out_of_core"),
            "overcommit": _mca.get("device.overcommit"),
        },
        "wave_pipeline": bench_device_pipeline(tiles, elems, batch, reps),
        "out_of_core_gemm": bench_device_ooc_gemm(
            m=gemm_m, n=gemm_m, k=gemm_k, mb=gemm_mb),
        # ptc-fuse: wave mega-kernelization launch economics (>= 5x
        # fewer DEVICE launches at bit-exact results is the gate)
        "wave_fuse": bench_device_fuse_gemm(),
    }
    if doc["oversubscribed"]:
        doc["caveat"] = (
            f"pipeline threads ({threads}) > cores "
            f"({doc['host']['cpu_count']}): the "
            "prefetch lane timeshares with the manager, so the overlap "
            "fraction measures scheduling luck, not true concurrency — "
            "stall accounting (what moved OFF the dispatch path) "
            "remains valid")
        sys.stderr.write(f"bench-device WARNING: {doc['caveat']}\n")
    return doc


# --------------------------------------------------------------- stream
def _stream_worker(rank, port, size, hops, reps, env, q):
    """One rank of the cross-rank device-to-device streaming sweep: a
    rank-hopping RW chain of device chores whose datum is a `size`-byte
    tile — every hop is a full PK_DEVICE cross-rank move (producer d2h →
    wire → consumer h2d), the exact path the streaming pipeline rewires.
    One persistent process pair serves all reps (testbandwidth's
    steady-state discipline: rep 0 carries session/compile setup and is
    reported apart)."""
    try:
        import os
        for k, v in env.items():
            os.environ[k] = v
        os.environ["JAX_PLATFORMS"] = "cpu"  # rank processes stay off the chip
        import parsec_tpu as pt
        from parsec_tpu.device import TpuDevice

        ctx = pt.Context(nb_workers=1)
        ctx.set_rank(rank, 2)
        ctx.comm_init(port)
        dev = TpuDevice(ctx)
        elems = max(1, size // 4)
        arr = np.zeros((2, elems), dtype=np.float32)
        ctx.register_linear_collection("A", arr, elem_size=size,
                                       nodes=2, myrank=rank)
        ctx.register_arena("t", size)
        k = pt.L("k")

        def build():
            tp = pt.Taskpool(ctx, globals={"NB": hops})
            tc = tp.task_class("Hop")
            tc.param("k", 0, pt.G("NB"))
            tc.affinity("A", k % 2)
            tc.flow("A", "RW",
                    pt.In(pt.Mem("A", 0), guard=(k == 0)),
                    pt.In(pt.Ref("Hop", k - 1, flow="A")),
                    pt.Out(pt.Ref("Hop", k + 1, flow="A"),
                           guard=(k < pt.G("NB"))),
                    arena="t")
            dev.attach(tc, tp, kernel=_stream_bump, reads=["A"],
                       writes=["A"], shapes={"A": (elems,)},
                       dtype=np.float32)
            return tp

        walls = []
        for rep in range(reps + 1):  # rep 0 = setup, reported apart
            tp = build()
            ctx.comm_fence()
            t0 = time.perf_counter()
            tp.run()
            tp.wait()
            ctx.comm_fence()
            walls.append(time.perf_counter() - t0)
        stream = ctx.comm_stream_stats()
        dstats = {k2: dev.stats.get(k2, 0) for k2 in
                  ("stream_serves", "stream_slices", "stream_d2h_ns",
                   "stream_bytes", "prefetch_wakeups", "dp_recv_bytes",
                   "h2d_stall_ns")}
        dev.stop()
        ctx.comm_fini()
        ctx.destroy()
        q.put(("ok", rank, walls, stream, dstats))
    except Exception:
        import traceback
        q.put(("err", rank, traceback.format_exc(), None, None))


def _stream_bump(x):
    # module-level: the process-wide jit cache keys on kernel identity
    return x + 1.0


def _stream_pair(size, hops, reps, port, stream, rails,
                 chunk=1 << 20, inflight=4):
    """Run one knob configuration on a fresh persistent 2-process pair;
    returns per-transfer latency + the producer-side span evidence."""
    import multiprocessing as mp
    env = {"PTC_MCA_comm_eager_limit": "0",
           "PTC_MCA_comm_chunk_size": str(chunk),
           "PTC_MCA_comm_inflight": str(inflight),
           "PTC_MCA_comm_stream": str(stream),
           "PTC_MCA_comm_rails": str(rails)}
    mpctx = mp.get_context("spawn")
    q = mpctx.Queue()
    procs = [mpctx.Process(target=_stream_worker,
                           args=(r, port, size, hops, reps, env, q))
             for r in range(2)]
    for p in procs:
        p.start()
    try:
        res = [q.get(timeout=900) for _ in range(2)]
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    errs = [r for r in res if r[0] != "ok"]
    if errs:
        raise RuntimeError(str(errs))
    by_rank = {r[1]: r for r in res}
    walls = [max(by_rank[0][2][i], by_rank[1][2][i])
             for i in range(reps + 1)]
    per = [w / hops for w in walls[1:]]
    best = min(per)
    # span evidence accumulates on BOTH ranks (each serves the hops it
    # produced): sum the windows for the pair-level overlap fraction
    s0, s1 = by_rank[0][3], by_rank[1][3]
    d2h = s0["d2h_ns"] + s1["d2h_ns"]
    overlap = s0["overlap_ns"] + s1["overlap_ns"]
    return {
        "size_bytes": size, "stream": bool(stream), "rails": rails,
        "setup_ms": round(walls[0] * 1e3, 2),
        "per_transfer_ms": round(best * 1e3, 3),
        "per_transfer_ms_all": [round(t * 1e3, 3) for t in per],
        "gbps": round(size * 8 / best / 1e9, 3),
        "sessions": s0["sessions"] + s1["sessions"],
        "parked_gets": s0["parked_gets"] + s1["parked_gets"],
        "d2h_ns": d2h, "wire_ns": s0["wire_ns"] + s1["wire_ns"],
        "overlap_ns": overlap,
        "overlap_fraction": round(overlap / d2h, 4) if d2h else None,
        "device": {r: by_rank[r][4] for r in (0, 1)},
    }


def bench_stream_tuned(size, hops, reps, base):
    """Plan-driven autotuning of the streamed cross-rank tile chain
    (ptc-tune): the fitted transfer-economics model proposes
    (chunk quantum x rails) vectors (analysis/tune.py price_stream),
    the top-k + the hand-tuned defaults are validated with REAL
    2-process pairs, and the winner persists keyed by (workload key,
    host fingerprint).  tuned_vs_default / beats_default follow the
    bench_check conventions (timing slacked, flag never relaxed)."""
    from parsec_tpu.analysis.tune import (TuneStore, host_fingerprint,
                                          propose_stream)
    from parsec_tpu.utils import params as _mca
    topk = 3 if reps >= 2 else 2        # see bench_collective_tuned
    rounds = 3 if reps >= 2 else 1
    props = propose_stream(size, hops, topk=topk)
    dk = {"comm.chunk_size": _mca.get("comm.chunk_size"),
          "comm.rails": _mca.get("comm.rails")}
    # interleaved rounds + median per candidate (see
    # bench_collective_tuned for the rationale)
    samples = {i: [] for i in range(len(props))}
    for rnd in range(rounds):
        for i, p in enumerate(props):
            r = _stream_pair(size, hops, reps,
                             base + 4 * (rnd * len(props) + i),
                             stream=1,
                             rails=int(p["knobs"]["comm.rails"]),
                             chunk=int(p["knobs"]["comm.chunk_size"]))
            samples[i].append(r["per_transfer_ms"])
    validated = [{"knobs": p["knobs"],
                  "predicted_ns": round(p["predicted_ns"]),
                  "per_transfer_ms": sorted(samples[i])[rounds // 2],
                  "per_transfer_ms_rounds": samples[i]}
                 for i, p in enumerate(props)]
    default = next(r for r in validated if r["knobs"] == dk)
    winner = min(validated, key=lambda r: (r["per_transfer_ms"],
                                           r["predicted_ns"]))
    ratio = (winner["per_transfer_ms"] / default["per_transfer_ms"]
             if default["per_transfer_ms"] else None)
    host = host_fingerprint()
    TuneStore().put(f"stream:{size}:{hops}:2", host, {
        "knobs": winner["knobs"],
        "predicted_ns": winner["predicted_ns"],
        "measured_s": winner["per_transfer_ms"] / 1e3,
        "critpath_ratio": None,
        "source": "bench-stream",
    })
    return {
        "workload": "device_tile_chain", "size_bytes": size,
        "hops": hops, "reps": reps, "host": host,
        "default_knobs": dk,
        "default_per_transfer_ms": default["per_transfer_ms"],
        "winner_knobs": winner["knobs"],
        "winner_per_transfer_ms": winner["per_transfer_ms"],
        "tuned_vs_default": round(ratio, 4) if ratio else None,
        "beats_default": bool(ratio is not None and ratio <= 1.0),
        "validated": validated,
        "persisted": True,
    }


def bench_stream_suite(size=4 << 20, hops=8, reps=3, chunk=1 << 20,
                       inflight=4):
    """The `make bench-stream` document (BENCH_stream.json): steady-
    state ≥4 MiB cross-rank device-to-device tile latency with the
    streaming pipeline ON (progressive serve + 2 rails) vs the
    serialized PR3 baseline (stream off, 1 rail), plus a rails=1 vs
    rails=2 sweep at fixed stream=on.  Per-hop span evidence (d2h
    window, wire window, their overlap) comes from the engine's stream
    stats; the acceptance ratio is streamed/serialized per-transfer
    latency (target <= 0.6).  Knobs + host provenance ride along — a
    1-core host is flagged per the bench_dispatch_mt convention (the
    producer's slicer, the comm threads and the consumer's prefetch
    lane must timeshare there, which caps the visible overlap)."""
    import os
    from parsec_tpu.utils import params as _mca
    base = int(os.environ.get("PTC_PORT", "31500"))
    # per rank: worker + comm thread + device manager + writeback +
    # prefetch lane, two ranks
    doc = {
        "bench": "stream",
        **host_provenance(threads=2 * 5),
        "knobs": {"comm_rails": int(_mca.get("comm.rails")),
                  "comm_chunk_size": chunk,
                  "comm_inflight": inflight,
                  "comm_stream": bool(_mca.get("comm.stream")),
                  "comm_eager_limit": 0,
                  "size_bytes": size, "hops": hops, "reps": reps},
    }
    doc["serialized"] = _stream_pair(size, hops, reps, base, stream=0,
                                     rails=1, chunk=chunk,
                                     inflight=inflight)
    doc["streamed"] = _stream_pair(size, hops, reps, base + 4, stream=1,
                                   rails=2, chunk=chunk,
                                   inflight=inflight)
    doc["rails1_streamed"] = _stream_pair(size, hops, reps, base + 8,
                                          stream=1, rails=1, chunk=chunk,
                                          inflight=inflight)
    # ptc-tune: model-proposed (chunk x rails) vectors validated with
    # real pairs on the same workload (ROADMAP item 5 evidence)
    doc["tuned"] = bench_stream_tuned(size, hops, max(1, reps - 1),
                                      base + 12)
    ser = doc["serialized"]["per_transfer_ms"]
    stm = doc["streamed"]["per_transfer_ms"]
    doc["stream_vs_serialized_ratio"] = round(stm / ser, 4) if ser else None
    doc["ratio_target"] = 0.6
    r1 = doc["rails1_streamed"]["gbps"]
    r2 = doc["streamed"]["gbps"]
    doc["rails2_vs_rails1_throughput"] = round(r2 / r1, 4) if r1 else None
    if doc["oversubscribed"]:
        doc["caveat"] = (
            f"pipeline threads ({doc['pipeline_threads']}) > cores "
            f"({doc['host']['cpu_count']}): the producer's d2h slicer, "
            "both comm threads and the consumer's prefetch lane "
            "timeshare, so the measured overlap/ratio understate what "
            "distinct cores deliver — re-run on a multicore host for "
            "the real pipeline number")
        sys.stderr.write(f"bench-stream WARNING: {doc['caveat']}\n")
    return doc


def _coll_bench_worker(rank, port, sizes, reps, trace_dir, env, q):
    """One rank of the 2-rank collective bench: k-split GEMM with a
    cross-rank panel reduction per message size (C = sum_r A_r @ B_r,
    the C matrix IS the reduced message), DAG-dependency chain baseline
    vs runtime-native streamed collective.  The largest size's final rep
    also runs at trace level 2 and saves per-mode .ptt files for the
    parent's lost-time / overlap analysis (the PR 5 acceptance
    evidence)."""
    try:
        import os
        for k2, v in env.items():
            os.environ[k2] = v
        os.environ["JAX_PLATFORMS"] = "cpu"  # rank processes stay off the chip
        import parsec_tpu as pt
        from parsec_tpu.algos.gemm import gemm_panel_reduce
        from parsec_tpu.profiling import take_trace

        ctx = pt.Context(nb_workers=1)
        ctx.set_rank(rank, 2)
        ctx.comm_init(port)
        Nc, K = 256, 128
        ks = K // 2
        rng = np.random.default_rng(11)
        sweep = []
        with ctx:
            for si, size in enumerate(sizes):
                M = max(2, size // (4 * Nc))
                a = rng.integers(-4, 4, size=(M, K)).astype(np.float32)
                b = rng.integers(-4, 4, size=(K, Nc)).astype(np.float32)
                a_slab = a[:, rank * ks:(rank + 1) * ks].copy()
                b_slab = b[rank * ks:(rank + 1) * ks].copy()
                ref = sum(a[:, r * ks:(r + 1) * ks] @ b[r * ks:(r + 1) * ks]
                          for r in range(2)).astype(np.float32)
                entry = {"size_bytes": M * Nc * 4}
                traced = trace_dir and si == len(sizes) - 1
                # 4 row panels: panel p's reduction overlaps panel
                # p+1's compute in coll mode (the mechanism under test;
                # more panels = finer pipelining but more per-task
                # overhead, which an oversubscribed host amplifies)
                prow = max(1, M // 4)
                for mode in ("chain", "coll"):
                    walls = []
                    for rep in range(reps + 1):  # rep 0 = warmup
                        trace_this = traced and rep == reps
                        if trace_this:
                            ctx.profile_enable(2)
                        ctx.comm_fence()
                        t0 = time.perf_counter()
                        c = gemm_panel_reduce(ctx, a_slab, b_slab,
                                              reduce=mode,
                                              panel_rows=prow)
                        ctx.comm_fence()
                        walls.append(time.perf_counter() - t0)
                        if trace_this:
                            take_trace(ctx).save(os.path.join(
                                trace_dir, f"{mode}_r{rank}.ptt"))
                    assert (c == ref).all(), mode  # bit-exact, both modes
                    entry[f"{mode}_ms"] = round(min(walls[1:]) * 1e3, 3)
                sweep.append(entry)
            st = ctx.coll_stats()
            ctx.comm_fini()
        ctx.destroy()
        q.put(("ok", rank, sweep, st))
    except Exception:
        import traceback
        q.put(("err", rank, traceback.format_exc(), None))


def _xla_psum_baseline(sizes, reps):
    """Whole-array shard_map/XLA all-reduce of the same payload sizes —
    the bulk-synchronous library-call baseline the runtime-native path
    replaces (2 virtual host devices stand in for the 2 ranks).  Jitted
    once per size so recorded times are steady-state collective cost,
    not retracing."""
    import functools
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    # a host-side baseline on 2 virtual CPU devices (the ranks are CPU
    # processes too), pinned even where a chip is attached
    jax.config.update("jax_platforms", "cpu")
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        return None  # jax initialized single-device before us
    mesh = Mesh(np.array(devs[:2]), ("sp",))
    out = {}

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("sp"),
                       out_specs=P(), check_vma=False)
    def psum2(s):
        return lax.psum(s[0], "sp")

    for size in sizes:
        elems = max(1, size // 8)  # 2 contributions of size/2 = size total
        xs = np.stack([np.random.default_rng(r)
                       .integers(-4, 4, size=elems).astype(np.float32)
                       for r in range(2)])
        ts = []
        for rep in range(reps + 1):  # rep 0 compiles
            t0 = time.perf_counter()
            np.asarray(psum2(xs))
            ts.append(time.perf_counter() - t0)
        out[str(size)] = round(min(ts[1:]) * 1e3, 3)
    return out


def _coll_trace_metrics(trace_dir, mode):
    """Merged-trace evidence for one gemm mode: PR 5 lost-time totals
    (comm_wait + coll_wait = wire starvation) and the compute/wire
    overlap fraction — |union(EXEC) ∩ union(wire in-flight)| over
    |union(wire in-flight)|, wire intervals from matched send->recv
    flow pairs post clock sync."""
    import os

    from parsec_tpu.profiling import Trace, lost_time
    from parsec_tpu.profiling.critpath import _union_ns
    from parsec_tpu.profiling.trace import KEY_EXEC

    traces = [Trace.load(os.path.join(trace_dir, f"{mode}_r{r}.ptt"))
              for r in range(2)]
    m = Trace.merge(traces)
    lt = lost_time(m)["totals"]
    t = m._spans_table()
    exec_iv = [(int(b), int(e))
               for b, e in t[t[:, 2] == KEY_EXEC][:, 7:9]]
    fl = m.flows()
    wire_iv = [(int(r[4]), int(r[5])) for r in fl if r[5] > r[4]]
    wire_ns = _union_ns(list(wire_iv))
    inter = (_union_ns(list(exec_iv)) + wire_ns
             - _union_ns(list(exec_iv) + list(wire_iv)))
    return {
        "lost_time_totals": {k: int(v) for k, v in lt.items()},
        "comm_plus_coll_wait_ns": int(lt["comm_wait"] + lt["coll_wait"]),
        "wire_inflight_ns": int(wire_ns),
        "matched_flows": int(len(fl)),
        "overlap_fraction": (round(inter / wire_ns, 4)
                             if wire_ns else None),
    }


def _run_coll_pair(sizes, reps, base, env, trace_dir=""):
    """Spawn the 2-rank collective bench pair (optionally under extra
    env — the ptc-tune knob spelling) and return {rank: result}."""
    import multiprocessing as mp
    mpctx = mp.get_context("spawn")
    q = mpctx.Queue()
    procs = [mpctx.Process(target=_coll_bench_worker,
                           args=(r, base, list(sizes), reps, trace_dir,
                                 dict(env), q))
             for r in range(2)]
    for p in procs:
        p.start()
    try:
        res = [q.get(timeout=900) for _ in range(2)]
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    errs = [r for r in res if r[0] != "ok"]
    if errs:
        raise RuntimeError(str(errs))
    return {r[1]: r for r in res}


def bench_collective_tuned(size, reps=2, base=31760):
    """Plan-driven autotuning of the runtime-native collective
    (ptc-tune, ROADMAP item 5): the closed-form transfer-economics
    model (analysis/tune.py price_collective) proposes topology x
    slicing vectors for the bench's largest reduction, the top-k (and
    the hand-tuned defaults) are validated with REAL 2-rank
    gemm_panel_reduce runs — knobs cross into the rank processes via
    their PTC_MCA_* env spelling — and the winner persists keyed by
    (workload key, host fingerprint).  tuned_vs_default is the
    bench_check trajectory row; beats_default the equal-direction
    flag; bit-exactness holds in every validation run (the worker
    asserts it)."""
    from parsec_tpu.analysis.tune import (TuneStore, host_fingerprint,
                                          knob_env, propose_collective)
    # schema-smoke runs (reps <= 1) shrink the validation matrix so the
    # tier-1 subprocess tests stay inside their budget; the committed
    # make bench-collective runs the full one
    topk = 3 if reps >= 2 else 2
    rounds = 3 if reps >= 2 else 1
    props = propose_collective(size, 2, topk=topk)
    from parsec_tpu.utils import params as _mca
    dk = {"coll.topo": _mca.get("coll.topo"),
          "coll.max_slices": _mca.get("coll.max_slices"),
          "comm.eager_limit": _mca.get("comm.eager_limit")}
    # interleaved validation rounds, median per candidate: a 1-core
    # box drifts round to round — interleaving keeps one candidate
    # from eating a whole bad stretch, the median keeps one lucky
    # round from crowning a winner
    samples = {i: [] for i in range(len(props))}
    for rnd in range(rounds):
        for i, p in enumerate(props):
            by_rank = _run_coll_pair(
                [size], reps, base + 4 * (rnd * len(props) + i),
                knob_env(p["knobs"]))
            samples[i].append(max(by_rank[0][2][0]["coll_ms"],
                                  by_rank[1][2][0]["coll_ms"]))
    validated = [{"knobs": p["knobs"],
                  "predicted_ns": round(p["predicted_ns"]),
                  "coll_ms": sorted(samples[i])[rounds // 2],
                  "coll_ms_rounds": samples[i]}
                 for i, p in enumerate(props)]
    default = next(r for r in validated if r["knobs"] == dk)
    winner = min(validated, key=lambda r: (r["coll_ms"],
                                           r["predicted_ns"]))
    ratio = (winner["coll_ms"] / default["coll_ms"]
             if default["coll_ms"] else None)
    host = host_fingerprint()
    store = TuneStore()
    store.put(f"coll:{size}:2", host, {
        "knobs": winner["knobs"],
        "predicted_ns": winner["predicted_ns"],
        "measured_s": winner["coll_ms"] / 1e3,
        "critpath_ratio": None,
        "source": "bench-collective",
    })
    return {
        "workload": "gemm_panel_reduce", "size_bytes": size,
        "reps": reps, "host": host,
        "default_knobs": dk, "default_coll_ms": default["coll_ms"],
        "winner_knobs": winner["knobs"],
        "winner_coll_ms": winner["coll_ms"],
        "tuned_vs_default": round(ratio, 4) if ratio else None,
        "beats_default": bool(ratio is not None and ratio <= 1.0),
        "validated": validated,
        "persisted": True,
    }


def bench_collective_suite(sizes=(64 << 10, 512 << 10, 2 << 20), reps=3):
    """The `make bench-collective` document (BENCH_collective.json):
    DAG-dependency reduction (chain baseline — whole-array partials, a
    serial rank chain, exactly how reductions were expressed before
    runtime-native collectives) vs the runtime-native streamed
    collective (panels feed the ptc_coll_* reduction as they complete)
    across message sizes on a 2-rank pair, plus the whole-array XLA
    shard_map psum baseline.  The largest size carries level-2 traces;
    the acceptance evidence is comm_wait+coll_wait SHRINKING and the
    compute/wire overlap fraction RISING for coll vs chain (ISSUE 6) —
    1-core containers are flagged per the bench_dispatch_mt
    oversubscription convention (all stages timeshare one core, which
    caps visible overlap)."""
    import os
    import tempfile

    from parsec_tpu.utils import params as _mca

    base = int(os.environ.get("PTC_PORT", "31700"))
    trace_dir = tempfile.mkdtemp(prefix="bench_coll_")
    by_rank = _run_coll_pair(list(sizes), reps, base, {}, trace_dir)
    sweep = []
    for i, size in enumerate(sizes):
        e0, e1 = by_rank[0][2][i], by_rank[1][2][i]
        entry = {"size_bytes": e0["size_bytes"]}
        for mode in ("chain", "coll"):
            entry[f"{mode}_ms"] = max(e0[f"{mode}_ms"], e1[f"{mode}_ms"])
        entry["coll_vs_chain_ratio"] = (
            round(entry["coll_ms"] / entry["chain_ms"], 4)
            if entry["chain_ms"] else None)
        sweep.append(entry)
    # per rank: 2 workers + comm thread
    doc = {
        "bench": "collective",
        **host_provenance(threads=2 * 2),
        "knobs": {
            "coll_topo": _mca.get("coll.topo"),
            "coll_slice": _mca.get("coll.slice"),
            "coll_max_slices": _mca.get("coll.max_slices"),
            "comm_chunk_size": _mca.get("comm.chunk_size"),
            "comm_rails": _mca.get("comm.rails"),
            "comm_stream": bool(_mca.get("comm.stream")),
            "sizes": list(sizes), "reps": reps, "nodes": 2,
        },
        "sweep": sweep,
        "coll_topology_ops": by_rank[0][3]["by_topo"],
    }
    gemm = {}
    for mode in ("chain", "coll"):
        gemm[mode] = _coll_trace_metrics(trace_dir, mode)
    waits = {m: gemm[m]["comm_plus_coll_wait_ns"]
             for m in ("chain", "coll")}
    gemm["wait_reduction"] = (
        round(1.0 - waits["coll"] / waits["chain"], 4)
        if waits["chain"] else None)
    ov = {m: gemm[m]["overlap_fraction"] for m in ("chain", "coll")}
    gemm["overlap_fraction_gain"] = (
        round(ov["coll"] - ov["chain"], 4)
        if ov["coll"] is not None and ov["chain"] is not None else None)
    doc["gemm_panel"] = gemm
    doc["xla_psum_ms"] = _xla_psum_baseline(sizes, reps)
    big = sweep[-1]
    doc["coll_vs_chain_ratio"] = big["coll_vs_chain_ratio"]
    # ptc-tune: model-proposed knob vectors validated with real runs
    # on the largest reduction (ROADMAP item 5 evidence)
    doc["tuned"] = bench_collective_tuned(sizes[-1],
                                          reps=max(1, reps - 1),
                                          base=base + 40)
    if doc["oversubscribed"]:
        doc["caveat"] = (
            f"bench threads ({doc['pipeline_threads']}) > cores "
            f"({doc['host']['cpu_count']}): both ranks' workers and "
            "comm threads timeshare, so panel compute cannot truly "
            "overlap the reduction wire — ratios and overlap fractions "
            "understate what distinct cores deliver, and the "
            "comm_wait+coll_wait totals INFLATE for the streamed mode "
            "(its many small deliveries tag the timesharing gaps as "
            "wire starvation) — wait_reduction is only meaningful on "
            "a multicore host")
        sys.stderr.write(f"bench-collective WARNING: {doc['caveat']}\n")
    return doc


def _topo_bench_worker(rank, port, spec, coll_bytes, reps, hops, elems,
                       delay_us, env, q):
    """One rank of the 4-rank two-island topo soak (ptc-topo).  The
    island emulator's per-peer recv delays make inter-island legs
    genuinely slow; the topology spec makes them PRICED as slow.  Two
    sections, one spawn:

      allreduce  ring vs hierarchical two-level all_reduce of the same
                 payload — bit-exact against the numpy reference in
                 BOTH modes, per-mode wall and per-class wire split
                 (the hier tree's whole point is fewer dcn bytes/legs)
      remap      the pair-chain DAG whose identity placement crosses
                 the DCN on every hop: identity run, then
                 Taskpool.run(remap=True) under plan.remap_ranks() —
                 measured per-class deltas for both, per-rank
                 wire_out_bound soundness, payload-term tightness,
                 bit-exactness asserted inside every task body
    """
    try:
        import os
        for k2, v in env.items():
            os.environ[k2] = v
        os.environ["PTC_MCA_comm_topology"] = spec
        os.environ["JAX_PLATFORMS"] = "cpu"  # rank processes stay off the chip
        import parsec_tpu as pt
        from parsec_tpu.comm import coll
        from parsec_tpu.comm.topology import TopologyModel
        from parsec_tpu.utils.faults import comm_fault_env, island_delay_map

        tmref = TopologyModel.parse(spec)
        nodes = tmref.nranks
        if delay_us:
            os.environ.update(comm_fault_env(
                delay_map=island_delay_map(rank, tmref, delay_us)))
        ctx = pt.Context(nb_workers=1)
        ctx.set_rank(rank, nodes)
        ctx.comm_init(port)
        res = {}

        def snap():
            return {c: row["bytes_sent"] for c, row in
                    ctx.comm_topo_stats()["classes"].items()}

        with ctx:
            # ---- section A: ring vs hier all_reduce ----
            celems = max(1, coll_bytes // 4)
            arrs = [np.random.default_rng(r)
                    .integers(-4, 4, size=celems).astype(np.float32)
                    for r in range(nodes)]
            ref = sum(arrs).astype(np.float32)
            ar = {}
            for topo in ("ring", "hier"):
                walls = []
                ctx.comm_fence()
                b0 = snap()
                for rep in range(reps + 1):  # rep 0 = warmup
                    ctx.comm_fence()
                    t0 = time.perf_counter()
                    out = coll.all_reduce(ctx, arrs[rank], topo=topo)
                    ctx.comm_fence()
                    walls.append(time.perf_counter() - t0)
                    assert (out == ref).all(), topo  # bit-exact
                b1 = snap()
                ar[topo] = {"ms": round(min(walls[1:]) * 1e3, 3),
                            "dcn_bytes": b1["dcn"] - b0["dcn"]}
            res["allreduce"] = ar

            # ---- section B: identity vs remapped pair chain ----
            data = np.arange(elems, dtype=np.float32)
            arr = np.tile(data, (nodes, 1))  # identical per-slot payload:
            # any ownership permutation reads identical bytes, so the
            # remapped run's bit-exactness is decided by the body asserts
            ctx.register_linear_collection("A", arr, elem_size=elems * 4,
                                           nodes=nodes, myrank=rank)
            ctx.register_arena("t", elems * 4)

            def build():
                tp = pt.Taskpool(ctx, globals={"NB": hops})
                c, k = pt.L("c"), pt.L("k")
                tc = tp.task_class("Hop")
                tc.param("c", 0, 1)
                tc.param("k", 0, pt.G("NB"))
                tc.affinity("A", c + 2 * (k % 2))
                tc.flow("A", "RW",
                        pt.In(pt.Mem("A", c), guard=(k == 0)),
                        pt.In(pt.Ref("Hop", c, k - 1, flow="A")),
                        pt.Out(pt.Ref("Hop", c, k + 1, flow="A"),
                               guard=(k < pt.G("NB"))),
                        arena="t")

                def body(view):
                    a = view.data("A", dtype=np.float32)
                    np.testing.assert_array_equal(a, data + view["k"])
                    a += 1.0

                tc.body(body)
                return tp

            tp = build()
            plan = tp.plan()
            b0 = snap()
            tp.run()
            tp.wait()
            ctx.comm_fence()
            b1 = snap()
            m_ident = {c: b1[c] - b0[c] for c in b1}
            # per-rank plan soundness: the measured per-class sends never
            # exceed the plan's classed wire_out_bound for this rank
            sound = all(m_ident[c] <= plan.wire_out_bound(rank, c)
                        for c in m_ident if c != "loopback")
            # payload-term tightness: on classes this rank sends bulk
            # over, the measured bytes sit within 25% of the modeled
            # payload (envelope + control stay in the noise at 256 KiB
            # hops)
            tm = plan._tmodel()
            payload = {c: 0 for c in m_ident}
            for (s, d), b in plan.edges_bytes.items():
                if s == rank:
                    payload[tm.class_of(s, d)] += b
            tight = all(abs(m_ident[c] - p) <= 0.25 * p
                        for c, p in payload.items() if p >= 65536)

            arr[:] = data  # k==0 owner reads bumped the collection
            tp2 = build()
            perm = tp2.plan().remap_ranks()
            b0 = snap()
            tp2.run(remap=True)
            tp2.wait()
            ctx.comm_fence()
            b1 = snap()
            assert tp2.remap_applied == perm, (tp2.remap_applied, perm)
            m_remap = {c: b1[c] - b0[c] for c in b1}
            res["remap"] = {
                "perm": perm,
                "measured_ident": m_ident,
                "measured_remap": m_remap,
                "payload_ident": payload,
                "predicted_ident": plan.class_bytes(),
                "predicted_remap": plan.class_bytes(perm=perm),
                "rank_sound": bool(sound),
                "rank_payload_within_25pct": bool(tight),
            }
            ctx.set_rank_map(None)
            ctx.comm_fence()
            ctx.comm_fini()
        ctx.destroy()
        q.put(("ok", rank, res))
    except Exception:
        import traceback
        q.put(("err", rank, traceback.format_exc()))


def _run_topo_quad(spec, coll_bytes, reps, hops, elems, delay_us, base,
                   env):
    """Spawn the 4-rank topo bench mesh and return {rank: result}."""
    import multiprocessing as mp
    from parsec_tpu.comm.topology import TopologyModel
    nodes = TopologyModel.parse(spec).nranks
    mpctx = mp.get_context("spawn")
    q = mpctx.Queue()
    procs = [mpctx.Process(target=_topo_bench_worker,
                           args=(r, base, spec, coll_bytes, reps, hops,
                                 elems, delay_us, dict(env), q))
             for r in range(nodes)]
    for p in procs:
        p.start()
    try:
        res = [q.get(timeout=900) for _ in range(nodes)]
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    errs = [r for r in res if r[0] != "ok"]
    if errs:
        raise RuntimeError(str(errs))
    return {r[1]: r[2] for r in res}


def bench_topo_suite(spec="0,1;2,3", coll_bytes=1 << 20, reps=3, hops=8,
                     elems=1 << 16, delay_us=500, base=29750):
    """Topology-tier suite (`make bench-topo` -> BENCH_topo.json): the
    4-rank two-island soak under the island emulator's per-peer recv
    delays.  Headline evidence: the searched rank remap cuts the
    MEASURED dcn bytes of the pair-chain DAG >= 30% vs identity (it
    drops them to ~zero), the plan's per-class byte split is sound
    (measured <= classed wire_out_bound on every rank, payload term
    within 25%), and every payload — hierarchical collectives included
    — stays bit-identical.  dcn_reduction / predicted_sound /
    bit_identical are the bench_check rows; walls are
    oversubscription-slacked trajectory rows (4 ranks timeshare one
    host)."""
    from parsec_tpu.comm.topology import LINK_CLASSES
    by_rank = _run_topo_quad(spec, coll_bytes, reps, hops, elems,
                             delay_us, base, {})
    nodes = len(by_rank)
    doc = {
        "bench": "topo",
        "when": time.strftime("%Y-%m-%dT%H:%M:%S"),
        **host_provenance(threads=nodes),
        "knobs": {"spec": spec, "coll_bytes": coll_bytes, "reps": reps,
                  "hops": hops, "elems": elems, "delay_us": delay_us},
    }
    # slowest rank's best wall per mode; dcn bytes summed over ranks
    ar = {}
    for topo in ("ring", "hier"):
        ar[f"{topo}_ms"] = max(r["allreduce"][topo]["ms"]
                               for r in by_rank.values())
        ar[f"dcn_bytes_{topo}"] = sum(r["allreduce"][topo]["dcn_bytes"]
                                      for r in by_rank.values())
    ar["hier_vs_ring"] = (round(ar["hier_ms"] / ar["ring_ms"], 4)
                          if ar["ring_ms"] else None)
    ar["dcn_ratio_hier_vs_ring"] = (
        round(ar["dcn_bytes_hier"] / ar["dcn_bytes_ring"], 4)
        if ar["dcn_bytes_ring"] else None)
    ar["bit_identical"] = True  # workers assert it per rep, both modes
    doc["allreduce"] = ar

    r0 = by_rank[0]["remap"]
    measured = {}
    for key in ("measured_ident", "measured_remap"):
        measured[key] = {c: sum(r["remap"][key][c]
                                for r in by_rank.values())
                         for c in LINK_CLASSES}
    ident_dcn = measured["measured_ident"]["dcn"]
    remap_dcn = measured["measured_remap"]["dcn"]
    reduction = (round(1.0 - remap_dcn / ident_dcn, 4)
                 if ident_dcn else None)
    doc["remap"] = {
        "perm": r0["perm"],
        "ident_dcn_bytes": ident_dcn,
        "remap_dcn_bytes": remap_dcn,
        "dcn_reduction": reduction,
        "predicted_ident": r0["predicted_ident"],
        "predicted_remap": r0["predicted_remap"],
        "measured_ident": measured["measured_ident"],
        "measured_remap": measured["measured_remap"],
        "predicted_sound": all(r["remap"]["rank_sound"]
                               for r in by_rank.values()),
        "payload_within_25pct": all(
            r["remap"]["rank_payload_within_25pct"]
            for r in by_rank.values()),
    }
    doc["bit_identical"] = True  # every body/collective assert passed
    # the acceptance floor — fail make bench-topo loudly, not in review
    assert reduction is not None and reduction >= 0.30, doc["remap"]
    assert doc["remap"]["predicted_sound"], doc["remap"]
    return doc


def bench_serve_suite(n_hi=6, n_lo=18, max_new=6, workers=2, seed=0,
                      n_pages=256, max_seqs=32, seq_check=2,
                      lo_prompt=(14, 28), hi_prompt=(3, 7), lo_new=10):
    """Serving-runtime suite (`make bench-serve` -> BENCH_serve.json).

    Mixed-tenant latency: the SAME request mix (n_hi high-priority + n_lo
    background requests, submitted together) runs twice through the
    Server + continuous-batching InferenceEngine —
      qos      hi tenant priority 4 / weight 4, lo tenant 0/1: the
               native SchedLWS lanes serve hi pools first at every wave
               boundary
      control  both tenants priority 0 / weight 1 (one shared FIFO
               lane — the no-QoS discipline)
    and the hi tenant's submit->done p99 must BEAT the control run's
    (recorded as qos.hi_p99_beats_control; the oversubscription caveat
    widens the in-document gate 3x, never the bit-exactness flags).

    Admission: a tight-budget run (max_pools/max_queue small) counts
    rejects + resource waits — backpressure exercised, not assumed.

    Correctness: every continuous-batched request's tokens/outputs are
    compared BIT-IDENTICALLY against the sequential per-request
    baseline (`seq_check` requests re-run one-at-a-time through a fresh
    engine; the rest against the numpy per-request oracle that shares
    the DAG's exact fold order)."""
    from parsec_tpu.serve import (InferenceEngine, PagedLM, PagedLMConfig,
                                  TenantConfig)

    # 8 virtual host devices BEFORE the first jax backend use: the tp
    # section pins one per colocated rank (up to 4) and the spec
    # section's fused-verify run takes device 0
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    cfg = PagedLMConfig(vocab=48, d=16, page=4, seed=7)
    model = PagedLM(cfg)
    rng = np.random.RandomState(seed)
    # background tenant: long prompts (many KV pages -> large decode
    # pools saturating the workers), more decode steps; hi tenant:
    # short interactive requests that must cut ahead of the queued
    # background waves.  lo requests submit FIRST, so hi latency
    # measures jumping a warm queue, not an empty runtime.
    reqs = []
    for _ in range(n_lo):
        prompt = list(rng.randint(0, cfg.vocab,
                                  size=int(rng.randint(*lo_prompt))))
        reqs.append((prompt, lo_new, "lo"))
    for _ in range(n_hi):
        prompt = list(rng.randint(0, cfg.vocab,
                                  size=int(rng.randint(*hi_prompt))))
        reqs.append((prompt, max_new, "hi"))
    n_hi_eff = n_hi

    def run_mix(hi_prio, hi_weight):
        with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
            eng = InferenceEngine(
                ctx, model, n_pages=n_pages, max_seqs=max_seqs,
                tenants=[
                    TenantConfig("hi", priority=hi_prio, weight=hi_weight,
                                 max_pools=max_seqs, max_queue=256),
                    TenantConfig("lo", priority=0, weight=1,
                                 max_pools=max_seqs, max_queue=256),
                ])
            t0 = time.perf_counter()
            handles = [eng.submit(p, n, t) for p, n, t in reqs]
            eng.run(timeout_s=600)
            wall = time.perf_counter() - t0
            sched = ctx.sched_stats()
            server = eng.server.stats()
            scope_st = ctx.stats()["scope"]  # ptc-scope rollup
            eng.close()
        lat = {"hi": [], "lo": []}
        outs = []
        for h, (_, _, t) in zip(handles, reqs):
            assert h.state == "done", (h.state, t)
            lat[t].append(h.latency_s * 1e3)
            outs.append((h.tokens, np.stack(h.outputs)))
        tokens = sum(len(h.generated) for h in handles)

        def pcts(v):
            v = sorted(v)
            return {
                "n": len(v),
                "p50_ms": round(v[len(v) // 2], 3),
                "p99_ms": round(v[min(len(v) - 1,
                                      int(len(v) * 0.99))], 3),
                "mean_ms": round(sum(v) / len(v), 3),
            }

        return {
            "hi": pcts(lat["hi"]),
            "lo": pcts(lat["lo"]),
            "wall_s": round(wall, 3),
            "throughput_tok_s": round(tokens / wall, 1),
            "qos_selects": sched["qos_selects"],
            "qos_preempts": sched["qos_preempts"],
            "server_totals": server["totals"],
            "_scope": scope_st,
        }, outs

    qos_doc, qos_outs = run_mix(4, 4)
    ctl_doc, ctl_outs = run_mix(0, 1)
    qos_scope = qos_doc.pop("_scope")
    ctl_doc.pop("_scope", None)

    # ---- correctness: continuous == sequential per-request, bit-exact
    bit_identical = True
    for i, (prompt, n, t) in enumerate(reqs):
        rt, ro = model.reference_generate(prompt, n)
        for doc_outs in (qos_outs, ctl_outs):
            toks, outs = doc_outs[i]
            if toks != rt or not np.array_equal(outs, ro):
                bit_identical = False
    seq_checked = 0
    for i in range(min(seq_check, len(reqs))):
        prompt, n, t = reqs[i]
        with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
            eng = InferenceEngine(ctx, model, n_pages=n_pages,
                                  max_seqs=2,
                                  tenants=[TenantConfig(t)])
            h = eng.submit(prompt, n, t)
            eng.run(timeout_s=120)
            eng.close()
        toks, outs = qos_outs[i]
        if h.tokens != toks or \
                not np.array_equal(np.stack(h.outputs), outs):
            bit_identical = False
        seq_checked += 1

    # ---- admission: tight budgets exercise queue + reject + backpressure
    with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
        eng = InferenceEngine(
            ctx, model, n_pages=12, max_seqs=3,
            tenants=[TenantConfig("t", max_pools=2, max_queue=3)])
        handles = [eng.submit([1, 2, 3, 4, 5], 3, "t") for _ in range(12)]
        eng.run(timeout_s=300)
        adm = eng.server.stats()["tenants"]["t"]
        eng.close()
    admission = {
        "submitted": adm["submitted"], "admitted": adm["admitted"],
        "rejected": adm["rejected"], "completed": adm["completed"],
        "resource_waits": adm["resource_waits"],
        "queue_wait_ms_mean": round(
            adm["queue_wait_ns"] / 1e6 / max(1, adm["admitted"]), 3),
    }

    doc = host_provenance(threads=workers + 1)  # workers + driver/pump
    oversub = doc.get("oversubscribed", False)
    gate = 3.0 if oversub else 1.0
    doc.update({
        "knobs": {"n_hi": n_hi_eff, "n_lo": len(reqs) - n_hi_eff,
                  "max_new": max_new, "workers": workers,
                  "n_pages": n_pages, "max_seqs": max_seqs,
                  "page": cfg.page, "d": cfg.d},
        "qos": dict(qos_doc,
                    hi_p99_beats_control=bool(
                        qos_doc["hi"]["p99_ms"] <
                        ctl_doc["hi"]["p99_ms"] * gate)),
        "control": ctl_doc,
        "hi_p99_improvement": round(
            ctl_doc["hi"]["p99_ms"] / qos_doc["hi"]["p99_ms"], 3),
        "admission": admission,
        "decode": {"bit_identical": bit_identical,
                   "requests": len(reqs),
                   "sequential_engine_checked": seq_checked},
        # ptc-scope: per-tenant SLO metrics + plan-vs-measured
        # conformance from the QoS run.  The `sound` flag is a
        # CORRECTNESS row in bench_check (full plan coverage AND no
        # pool finishing below its makespan lower bound) — never
        # relaxed by oversubscription; the latency/rate rows are
        # trajectory-guarded timing
        "scope": _scope_bench_section(qos_scope),
        # ptc-share: shared-prefix KV cache (cold vs warm prompt mix)
        # and speculative decoding (off / k=2 / k=4 + the fused verify
        # wave) — the bit_identical flags are equal-direction
        # correctness rows bench_check NEVER relaxes; hit-rate and
        # tokens/s are oversubscription-slacked timing trajectory rows
        "prefix": _prefix_bench_section(model, workers=workers),
        "spec": _spec_bench_section(model, workers=workers),
        # ptc-route: 1 vs 2 replicas behind the fleet router —
        # aggregate tokens/s scaling and global hit rate are
        # oversubscription-slacked timing trajectory rows; the
        # routed-vs-single bit_identical flag is an equal-direction
        # correctness row bench_check NEVER relaxes
        "fleet": _fleet_bench_section(model, workers=workers),
        # ptc-shard: 2- and 4-rank tensor-parallel PagedLM vs the
        # single-rank reference — bit_identical and the every-rank-
        # fused-waves verdict are equal-direction correctness flags
        # bench_check NEVER relaxes; the per-token wall ratio is an
        # oversubscription-slacked timing trajectory row (all ranks
        # timeshare this host)
        "tp": _tp_bench_section(workers=workers),
    })
    if oversub:
        doc["caveat"] = (
            "pipeline threads exceed physical cores: tenant latency "
            "separation measures scheduling under timesharing; the "
            "hi-p99 gate is widened 3x (bit-exactness flags never are)")
    return doc


def _scope_bench_section(scope_st):
    """BENCH_serve scope section off a Context.stats()["scope"]
    snapshot: tenant TTFT/tokens-per-s quantiles + the conformance
    soundness verdict."""
    tenants = scope_st.get("tenants", {})

    def per_tenant(key, scale):
        return {name: round(row.get(key, 0) * scale, 3)
                for name, row in tenants.items()}

    conf = scope_st.get("conformance", {})
    cov = conf.get("coverage")
    rmin = (conf.get("makespan") or {}).get("ratio_min")
    sound = bool(cov == 1.0 and (rmin is None or rmin >= 1.0))
    return {
        "ttft_p99_ms": per_tenant("ttft_ns_p99", 1e-6),
        "ttft_p50_ms": per_tenant("ttft_ns_p50", 1e-6),
        "tokens_per_s_p50": per_tenant("tokens_per_s_p50", 1.0),
        "queue_wait_p99_ms": per_tenant("queue_wait_ns_p99", 1e-6),
        "conformance": {
            "coverage": cov,
            "makespan_ratio_p50": (conf.get("makespan") or
                                   {}).get("ratio_p50"),
            "makespan_ratio_min": rmin,
            "per_class_classes": len(conf.get("per_class") or {}),
            "sound": sound,
        },
    }


def _prefix_bench_section(model, workers=2, groups=4, per_group=4,
                          seed=17):
    """ptc-share prefix-cache section: `groups` distinct 4-page common
    prefixes are seeded cold (freezing their pages), then a WARM mix of
    `groups * per_group` requests re-using them runs on the live cache
    vs the identical mix on a cache-OFF control engine.  Records the
    warm hit rate, pages prefilled warm vs cold (the fewer-prefill-
    waves evidence) and warm vs no-cache tokens/s; `bit_identical`
    compares warm outputs against the control AND the numpy oracle."""
    from parsec_tpu.serve import InferenceEngine, TenantConfig

    cfg = model.cfg
    rng = np.random.RandomState(seed)
    common = [list(rng.randint(0, cfg.vocab, size=4 * cfg.page))
              for _ in range(groups)]
    seeds = [(c, 3, "t") for c in common]
    warm_reqs = []
    for g in range(groups):
        for _ in range(per_group):
            tail = list(rng.randint(0, cfg.vocab,
                                    size=int(rng.randint(0, 4))))
            warm_reqs.append((common[g] + tail, 5, "t"))

    def run_mix(prefix_cache):
        with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
            eng = InferenceEngine(
                ctx, model, n_pages=512, max_seqs=64,
                tenants=[TenantConfig("t", max_pools=64, max_queue=256)],
                prefix_cache=prefix_cache)
            hs0 = [eng.submit(p, n, t) for p, n, t in seeds]
            eng.run(timeout_s=300)
            st0 = eng.pool.stats()
            t0 = time.perf_counter()
            hs = [eng.submit(p, n, t) for p, n, t in warm_reqs]
            eng.run(timeout_s=300)
            wall = time.perf_counter() - t0
            st = eng.pool.stats()
            eng.close()
        assert all(h.state == "done" for h in hs0 + hs)
        tokens = sum(len(h.generated) for h in hs)
        outs = [(h.tokens, np.stack(h.outputs)) for h in hs]
        return {
            "hits": st["prefix_hits"] - st0["prefix_hits"],
            "misses": st["prefix_misses"] - st0["prefix_misses"],
            "shared_bytes": st["shared_bytes"],
            "cow_copies": st["cow_copies"],
            "tokens_per_s": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
        }, outs

    warm_doc, warm_outs = run_mix(True)
    ctl_doc, ctl_outs = run_mix(False)
    bit_identical = True
    for (wt, wo), (ct, co), (p, n, _t) in zip(warm_outs, ctl_outs,
                                              warm_reqs):
        rt, ro = model.reference_generate(p, n)
        if wt != rt or ct != rt or not np.array_equal(wo, ro) or \
                not np.array_equal(co, ro):
            bit_identical = False
    hits, misses = warm_doc["hits"], warm_doc["misses"]
    return {
        "groups": groups, "per_group": per_group,
        "hit_rate": round(hits / max(1, hits + misses), 4),
        "pages_prefilled_warm": misses,
        "pages_prefilled_cold": ctl_doc["hits"] + ctl_doc["misses"],
        "fewer_prefill_than_cold": bool(
            misses < ctl_doc["hits"] + ctl_doc["misses"]),
        "shared_bytes": warm_doc["shared_bytes"],
        "cow_copies": warm_doc["cow_copies"],
        "warm_tokens_per_s": warm_doc["tokens_per_s"],
        "nocache_tokens_per_s": ctl_doc["tokens_per_s"],
        "bit_identical": bit_identical,
    }


def _spec_bench_section(model, workers=2, n_reqs=8, max_new=8, seed=23):
    """ptc-share speculative-decoding section: the SAME request mix
    decodes with speculation OFF and at k=2 / k=4 (oracle self-draft —
    the acceptance upper bound), recording tokens/s, verify waves vs
    tokens (the fewer-waves evidence) and draft acceptance;
    `bit_identical` compares every speculative output stream against
    the non-speculative run.  `verify_wave` runs one device-attached
    k=4 mix and counts paired DEVICE spans: the batched verification's
    VATF waves dispatch FUSED (begin-aux marked) — launches well under
    task count."""
    from parsec_tpu.profiling.trace import KEY_DEVICE
    from parsec_tpu.serve import InferenceEngine, TenantConfig

    cfg = model.cfg
    rng = np.random.RandomState(seed)
    reqs = [(list(rng.randint(0, cfg.vocab,
                              size=int(rng.randint(6, 18)))),
             max_new, "t") for _ in range(n_reqs)]

    def run_k(k, dev=False, trace=False):
        with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
            if trace:
                ctx.profile_enable(1)
            dev_obj = None
            if dev:
                from parsec_tpu.device import TpuDevice
                dev_obj = TpuDevice(ctx)
            try:
                eng = InferenceEngine(
                    ctx, model, n_pages=512, max_seqs=32,
                    tenants=[TenantConfig("t", max_pools=32,
                                          max_queue=256)],
                    spec_k=k, dev=dev_obj)
                t0 = time.perf_counter()
                hs = [eng.submit(p, n, t) for p, n, t in reqs]
                eng.run(timeout_s=300)
                wall = time.perf_counter() - t0
                st = dict(eng.stats)
                serve_spec = eng._spec_stats()
                fuse = ctx.device_stats().get("fuse", {}) if dev else {}
                ev = ctx.profile_take() if trace else None
                eng.close()
            finally:
                if dev_obj is not None:
                    dev_obj.stop()
        assert all(h.state == "done" for h in hs)
        tokens = sum(len(h.generated) for h in hs)
        return {
            "tokens": tokens,
            "tokens_per_s": round(tokens / wall, 1),
            "decode_waves": st["decode_pools"],
            "accept_rate": round(serve_spec["accept_rate"], 4),
            "fallbacks": st["spec_fallbacks"],
        }, [(h.tokens, np.stack(h.outputs)) for h in hs], fuse, ev

    base, base_outs, _, _ = run_k(0)
    out = {"off": base}
    bit_identical = True
    for k in (2, 4):
        doc, outs, _, _ = run_k(k)
        for (st_, so), (bt, bo) in zip(outs, base_outs):
            if st_ != bt or not np.array_equal(so, bo):
                bit_identical = False
        doc["waves_vs_tokens"] = round(
            doc["decode_waves"] / max(1, doc["tokens"]), 3)
        out[f"k{k}"] = doc
    out["bit_identical"] = bit_identical
    out["fewer_waves_than_off"] = bool(
        out["k4"]["decode_waves"] < base["decode_waves"])
    # fused verify wave: DEVICE span evidence (device folds = VATF
    # verification only; PATTL/VATL run host-side)
    vdoc, _, fuse, ev = run_k(4, dev=True, trace=True)
    spans = _pair_spans(ev, KEY_DEVICE) if ev is not None else []
    fused_marked = sum(1 for s in spans if s[4] > 0)
    out["verify_wave"] = {
        "device_launches": len(spans),
        "fused_marked_launches": fused_marked,
        "fused_waves": fuse.get("fused_waves", 0),
        "fused_tasks": fuse.get("fused_tasks", 0),
        "single_fused_launch": bool(
            fuse.get("fused_waves", 0) > 0 and
            fuse.get("fused_tasks", 0) > fuse.get("fused_waves", 0)),
        "tokens_per_s": vdoc["tokens_per_s"],
    }
    return out


def _fleet_bench_section(model, workers=2, groups=3, per_group=4,
                         max_new=5, seed=29):
    """ptc-route fleet section: the SAME shared-prefix request mix runs
    through ONE engine and through TWO replicas behind a Router
    (prefix-locality scored placement, page migration priced in).
    Records aggregate tokens/s for both (scaling = fleet / single),
    the GLOBAL fleet prefix hit rate vs the single replica's, and a
    routed-vs-single bit_identical flag — the correctness row
    bench_check NEVER relaxes.  Both runs share one process's cores,
    so scaling is an efficiency trajectory (oversubscription-slacked),
    not a speedup claim."""
    from parsec_tpu.serve import (InferenceEngine, Replica, Router,
                                  TenantConfig)

    cfg = model.cfg
    rng = np.random.RandomState(seed)
    common = [list(rng.randint(0, cfg.vocab, size=3 * cfg.page))
              for _ in range(groups)]
    reqs = []
    for g in range(groups):
        for _ in range(per_group):
            tail = list(rng.randint(0, cfg.vocab,
                                    size=int(rng.randint(1, 4))))
            reqs.append((common[g] + tail, max_new, "t"))

    def pool_rate(*stats):
        hits = sum(s["prefix_hits"] for s in stats)
        misses = sum(s["prefix_misses"] for s in stats)
        return round(hits / max(1, hits + misses), 4)

    # ---- single replica baseline
    with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
        eng = InferenceEngine(
            ctx, model, n_pages=256, max_seqs=32,
            tenants=[TenantConfig("t", max_pools=32, max_queue=256)])
        t0 = time.perf_counter()
        hs = [eng.submit(p, n, t) for p, n, t in reqs]
        eng.run(timeout_s=300)
        single_wall = time.perf_counter() - t0
        single_stats = eng.pool.stats()
        eng.close()
    assert all(h.state == "done" for h in hs)
    tokens = sum(len(h.generated) for h in hs)
    single_outs = [(h.tokens, np.stack(h.outputs)) for h in hs]
    single_tok_s = tokens / single_wall

    # ---- 2 replicas behind the router
    ctxs = [pt.Context(nb_workers=workers, scheduler="lws")
            for _ in range(2)]
    try:
        reps = [Replica(InferenceEngine(
            c, model, n_pages=256, max_seqs=32,
            tenants=[TenantConfig("t", max_pools=32, max_queue=256)],
            name=f"r{i}")) for i, c in enumerate(ctxs)]
        router = Router(reps)
        t0 = time.perf_counter()
        fhs = [router.submit(p, n, tenant=t) for p, n, t in reqs]
        router.run(timeout_s=300)
        fleet_wall = time.perf_counter() - t0
        fleet_stats = [r.pool.stats() for r in reps]
        rstats = router.stats()
        # ptc-blackbox: FleetView federation cost over these replicas
        # (merge of every tenant histogram + replica advertise), the
        # price of one /fleet.json refresh
        from parsec_tpu.profiling.blackbox import FleetView
        fv = FleetView(servers=[r.server for r in reps], start=False)
        n_scrapes = 20
        t0 = time.perf_counter()
        for _ in range(n_scrapes):
            fv.scrape_once()
        scrape_ms = (time.perf_counter() - t0) / n_scrapes * 1e3
        fv.stop()
        router.close()
    finally:
        for c in ctxs:
            c.destroy()
    assert all(fh.state == "done" for fh in fhs)
    fleet_tokens = sum(len(fh.generated) for fh in fhs)
    fleet_tok_s = fleet_tokens / fleet_wall
    bit_identical = True
    for fh, (st_, so), (p, n, _t) in zip(fhs, single_outs, reqs):
        rt, ro = model.reference_generate(p, n)
        if fh.tokens != st_ or fh.tokens != rt or \
                not np.array_equal(np.stack(fh.outputs), so) or \
                not np.array_equal(np.stack(fh.outputs), ro):
            bit_identical = False
    return {
        "replicas": 2, "requests": len(reqs),
        "groups": groups, "per_group": per_group,
        "single_tokens_per_s": round(single_tok_s, 1),
        "fleet_tokens_per_s": round(fleet_tok_s, 1),
        "scaling": round(fleet_tok_s / max(1e-9, single_tok_s), 3),
        "single_hit_rate": pool_rate(single_stats),
        "hit_rate": pool_rate(*fleet_stats),
        "placed": rstats["router"]["placed"],
        "migrated_pages": rstats["router"]["migrated_pages"],
        "migrated_bytes": rstats["router"]["migrated_bytes"],
        "bit_identical": bit_identical,
        "fleet_scrape_ms": round(scrape_ms, 3),
    }


def _tp_bench_section(workers=2, max_new=6, n_reqs=3, seed=23,
                      base_port=29930):
    """ptc-shard tensor-parallel section: the SAME request mix (shared
    prefix + speculative decoding k=2 both LIVE) decodes on a 1-rank
    reference engine and on 2- and 4-rank colocated tp groups — a
    heads=4 qlog PagedLM with head-sharded KV pages, the per-rank
    partial pre-logit projections summed by the RefReduce chain
    embedded in every decode/prefill/verify pool, and SPMD next-token
    selection off the fanned-out reduction.  Records:

      bit_identical   every tp degree reproduces the single-rank
                      reference AND the numpy oracle — tokens and the
                      exact f32 pre-logit bytes (the qlog dyadic grids
                      make the split reduction exact in any
                      association) — equal-direction, never relaxed
      tpN.ms_per_token  decode wall per generated token; flat-ish as tp
                      grows is the win, but all ranks timeshare one
                      host so this is oversubscription-slacked timing
      tpN.fused_waves per-rank PR 13 wave-compiler counts from a
                      separate device-attached run of the same mix
                      (each rank certifies + fuses ITS OWN shard of
                      the batched verify wave); all_ranks_fused is the
                      fused_waves>0-on-every-rank verdict —
                      equal-direction, never relaxed
      tpN.coll_wait_ms  total engine stall on the embedded collective
    """
    import threading

    from parsec_tpu.serve import InferenceEngine, PagedLM, PagedLMConfig

    cfg = PagedLMConfig(heads=4, qlog=True, seed=11)
    model = PagedLM(cfg)
    rng = np.random.RandomState(seed)
    common = list(rng.randint(0, cfg.vocab, size=2 * cfg.page))
    reqs = [(common + list(rng.randint(0, cfg.vocab,
                                       size=int(rng.randint(0, 6)))),
             max_new) for _ in range(n_reqs)]
    oracle = [model.reference_generate(p, m) for p, m in reqs]

    def drive(eng):
        hs = []
        t0 = time.monotonic()
        for p, m in reqs:
            h = eng.submit(p, m)
            hs.append(h)
            while h.state == "submitted":
                if time.monotonic() - t0 > 120:
                    raise TimeoutError("prefill stuck")
                time.sleep(0.001)
        while eng.pending() or eng._inflight:
            if time.monotonic() - t0 > 240:
                raise TimeoutError("decode stuck")
            eng.step()
        return hs

    def run_group(nodes, port, with_dev=False):
        results = {}

        def worker(rank):
            try:
                ctx = pt.Context(nb_workers=1)
                ctx.set_rank(rank, nodes)
                ctx.comm_init(port)
                ctx.comm_set_colocated(
                    [r for r in range(nodes) if r != rank])
                with ctx:
                    dev = None
                    if with_dev:
                        import jax

                        from parsec_tpu.device import TpuDevice
                        jd = jax.devices()
                        dev = TpuDevice(ctx,
                                        jax_device=jd[rank % len(jd)])
                    try:
                        eng = InferenceEngine(
                            ctx, model, n_pages=128, max_seqs=8,
                            tp=nodes, spec_k=2, dev=dev)
                        t0 = time.perf_counter()
                        hs = drive(eng)
                        wall = time.perf_counter() - t0
                        st = dict(eng.stats)
                        fuse = (ctx.device_stats().get("fuse", {})
                                if with_dev else {})
                        toks = [list(h.tokens) for h in hs]
                        outs = [[o.copy() for o in h.outputs]
                                for h in hs]
                        eng.close()
                    finally:
                        if dev is not None:
                            dev.stop()
                    ctx.comm_fence()
                    ctx.comm_fini()
                results[rank] = ("ok", toks, outs, wall, st, fuse)
            except Exception:
                import traceback
                results[rank] = ("err", traceback.format_exc(),
                                 None, None, None, None)

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(nodes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=280)
        for r in range(nodes):
            st = results.get(r, ("missing", None))
            assert st[0] == "ok", f"tp{nodes} rank {r}: {st[1]}"
        return results

    # ---- single-rank reference (same mix, prefix + spec on)
    with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
        eng = InferenceEngine(ctx, model, n_pages=128, max_seqs=8,
                              spec_k=2)
        t0 = time.perf_counter()
        hs = drive(eng)
        ref_wall = time.perf_counter() - t0
        eng.close()
    tokens = sum(len(h.generated) for h in hs)
    ref_toks = [list(h.tokens) for h in hs]
    ref_pre = [[model.pre_logits(o) for o in h.outputs] for h in hs]
    bit_identical = True
    for i, ((p, m), (ot, oo)) in enumerate(zip(reqs, oracle)):
        if ref_toks[i] != ot:
            bit_identical = False
        for j in range(m):
            if not np.array_equal(ref_pre[i][j], model.pre_logits(oo[j])):
                bit_identical = False

    doc = {"requests": len(reqs), "tokens": tokens,
           "heads": cfg.heads, "d": cfg.d,
           "tp1": {"ms_per_token": round(ref_wall * 1e3 / tokens, 3)}}
    all_fused = True
    for i, nodes in enumerate((2, 4)):
        res = run_group(nodes, base_port + 4 * i)
        # every rank's tokens + reduced pre-logit bytes must equal the
        # single-rank reference (and, transitively, the oracle)
        for r in range(nodes):
            if res[r][1] != ref_toks:
                bit_identical = False
            for o_tp, o_ref in zip(res[r][2], ref_pre):
                for a, b in zip(o_tp, o_ref):
                    if not np.array_equal(a, b):
                        bit_identical = False
        wall = max(res[r][3] for r in range(nodes))
        st = res[0][4]
        fres = run_group(nodes, base_port + 4 * i + 2, with_dev=True)
        fused = [fres[r][5].get("fused_waves", 0) for r in range(nodes)]
        if not all(f > 0 for f in fused):
            all_fused = False
        doc[f"tp{nodes}"] = {
            "ms_per_token": round(wall * 1e3 / tokens, 3),
            "coll_pools": st["tp_coll_pools"],
            "coll_wait_ms": round(st["tp_coll_wait_ns"] / 1e6, 3),
            "prefix_hits": st["prefix_hits"],
            "spec_accepted": st["spec_accepted"],
            "fused_waves": fused,
        }
    doc["bit_identical"] = bit_identical
    doc["all_ranks_fused"] = all_fused
    doc["tp4_vs_tp1_ms_per_token"] = round(
        doc["tp4"]["ms_per_token"] / max(1e-9,
                                         doc["tp1"]["ms_per_token"]), 3)
    return doc


def _control_soak_section(m=384, k=64, mb=32, reps=3,
                          fault_delay_us=2000):
    """ptc-pilot drift soak: an out-of-core-capable GEMM runs healthy,
    then an incident lands mid-run — the comm fault hook is armed
    (PTC_COMM_FAULT_DELAY_US, delaying every native recv of any comm
    engine brought up from here on) and the tuned knob vector goes
    STALE: device.cache_bytes pinned to a quarter of the tile set, the
    classic workload-outgrew-its-tuning shape.  Every rep now thrashes
    the device cache (hundreds of real spill/re-stage memcpys — the
    per-recv delay itself needs a live comm engine to bite, so on this
    single-rank soak the measurable damage is the stale vector).  A
    Controller on a long-lived control-plane context observes each rep
    through ScopeRegistry.record_pool_done, detects the sustained
    makespan drift, re-simulates on the recalibrated model (the
    simulator prices the thrash via Plan.predict_spills) and hot-swaps
    the winning vector — the uncapped budget — at the next pool
    boundary.  `recovery_ratio` is the fraction of incident-lost
    throughput the swap claws back WITHOUT a restart — the gated
    claim."""
    import os

    from parsec_tpu.algos import build_gemm
    from parsec_tpu.analysis.control import Controller
    from parsec_tpu.analysis.tune import TuneStore, hold_knobs
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device import TpuDevice

    def _gemm(ctx, dev):
        rng = np.random.default_rng(3)
        A = TwoDimBlockCyclic(m, k, mb, mb, dtype=np.float32)
        B = TwoDimBlockCyclic(k, m, mb, mb, dtype=np.float32)
        Cc = TwoDimBlockCyclic(m, m, mb, mb, dtype=np.float32)
        A.from_dense(rng.standard_normal((m, k), dtype=np.float32))
        B.from_dense(rng.standard_normal((k, m), dtype=np.float32))
        Cc.from_dense(np.zeros((m, m), np.float32))
        A.register(ctx, "A")
        B.register(ctx, "B")
        Cc.register(ctx, "C")
        return build_gemm(ctx, A, B, Cc, dev=dev)

    spill_log = []

    def _rep():
        """One pool: a fresh context + device (the device reads the
        LIVE device.cache_bytes knob, so a hot-swapped budget binds at
        the next rep — the pool boundary)."""
        with pt.Context(nb_workers=2) as ctx:
            dev = TpuDevice(ctx)
            try:
                tp = _gemm(ctx, dev)
                t0 = time.perf_counter()
                tp.run()
                tp.wait()
                dev.flush()
                wall = time.perf_counter() - t0
                spill_log.append(ctx.device_stats()["spills"])
            finally:
                dev.stop()
        return wall

    nt = (m // mb) * (m // mb) * (k // mb)

    def _tput(walls):
        return round(nt / sorted(walls)[len(walls) // 2], 1)

    store_path = "/tmp/ptc_bench_control_tuned.json"
    try:
        os.unlink(store_path)
    except OSError:
        pass

    _rep()  # untimed warmup: populate the executable caches
    with pt.Context(nb_workers=1) as cctx:
        reg = cctx.scope_registry()
        # phase A: healthy baseline, and the healthy makespan ratio the
        # drift threshold is calibrated against (the default cost
        # model's bound is loose on this host, so an absolute 1.25
        # would misread a slow box as drift)
        walls_a = [_rep() for _ in range(reps)]
        t_base = _tput(walls_a)

        ctrl = Controller(cctx, window=reps, cooldown=2,
                          store=TuneStore(store_path))
        target_dev = TpuDevice(cctx)   # graph construction only
        try:
            plan = ctrl.attach_target(_gemm(cctx, target_dev),
                                      workers=2)
            plan_sum = reg.plan_summary(plan)
            lb_ns = max(1, plan_sum["makespan_lb_ns"])
            healthy_ratio = sorted(walls_a)[reps // 2] * 1e9 / lb_ns
            ctrl.drift_ratio = 1.35 * healthy_ratio

            # the incident: armed comm fault injection + the stale
            # cache budget (a quarter of the GEMM tile set)
            from parsec_tpu.utils.faults import apply_comm_faults
            apply_comm_faults(delay_us=fault_delay_us)
            stale = (m * k + k * m + m * m) * 4 // 4
            _applied, restore_incident = hold_knobs(
                {"device.cache_bytes": stale})
            try:
                # phase B: degraded reps, each one a planned pool the
                # controller observes; the window fills, drift fires,
                # the retune proposal goes pending
                walls_b = []
                for _ in range(reps):
                    w = _rep()
                    walls_b.append(w)
                    sid = reg.new_scope("soak", kind="decode_step")
                    reg.record_pool_done(sid, plan=dict(plan_sum),
                                         measured={"wall_ns": w * 1e9})
                t_fault = _tput(walls_b)
                # the next pool boundary applies the pending swap
                ctrl.observe_pool(None)
                s = ctrl.stats()

                # phase C: recovered reps under the controller's vector
                walls_c = [_rep() for _ in range(reps)]
                t_rec = _tput(walls_c)
            finally:
                ctrl.stop()        # restores the pre-swap (incident) knobs
                restore_incident()  # lifts the incident hold itself
                os.environ.pop("PTC_COMM_FAULT_DELAY_US", None)
        finally:
            target_dev.stop()

        lost = max(1e-9, t_base - t_fault)
        recovery = round(max(0.0, min(1.5, (t_rec - t_fault) / lost)), 3)
        return {
            "m": m, "k": k, "mb": mb, "tasks": nt, "reps": reps,
            "fault_delay_us": fault_delay_us,
            "stale_cache_bytes": stale,
            "healthy_ratio": round(healthy_ratio, 3),
            "drift_ratio": round(ctrl.drift_ratio, 3),
            "throughput_tasks_s": {"healthy": t_base, "faulted": t_fault,
                                   "recovered": t_rec},
            "spills_per_phase": {
                "healthy": spill_log[1:1 + reps],
                "faulted": spill_log[1 + reps:1 + 2 * reps],
                "recovered": spill_log[1 + 2 * reps:]},
            "recovery_ratio": recovery,
            "recovered": bool(recovery >= 0.5 and s["swaps"] >= 1),
            "retunes": s["retunes"], "swaps": s["swaps"],
            "persisted": s["persisted"],
            "last_swap": s["last_swap"],
            "decisions": [d["kind"] for d in ctrl.decision_log()],
        }


def _control_spec_section(workers=2, n_reqs=4, max_new=40, seed=31):
    """ptc-pilot adaptive-speculation sweep: the SAME request mix runs
    against an ORACLE draft (self — acceptance 1.0) and an ADVERSARIAL
    draft (a differently-seeded model — acceptance ~0), at every fixed
    k and with spec_k='auto'.  No fixed k wins both mixes: high k is
    free latency on the oracle and pure wasted verify compute on the
    adversary.  The score is deterministic (counts, not wall time):
    tokens-per-verify-wave (latency win) normalized by wasted verify
    positions per token (compute cost) summed over both mixes —
    adaptive must beat every fixed k, with every stream bit-identical
    to plain decode."""
    from parsec_tpu.serve import InferenceEngine, TenantConfig
    from parsec_tpu.serve.engine import PagedLM, PagedLMConfig

    model = PagedLM(PagedLMConfig(vocab=32, d=8, page=4, seed=5))
    adversary = PagedLM(PagedLMConfig(vocab=32, d=8, page=4, seed=99))
    rng = np.random.RandomState(seed)
    reqs = [(list(rng.randint(0, 32, size=int(rng.randint(5, 12)))),
             max_new, "t") for _ in range(n_reqs)]

    def run_one(k, draft):
        with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
            eng = InferenceEngine(
                ctx, model, n_pages=256, max_seqs=8,
                tenants=[TenantConfig("t", max_pools=32, max_queue=64)],
                spec_k=k, spec_draft=draft)
            t0 = time.perf_counter()
            hs = [eng.submit(p, n, t) for p, n, t in reqs]
            eng.run(timeout_s=300)
            wall = time.perf_counter() - t0
            st = dict(eng.stats)
            sp = eng._spec_stats()
            events = len(ctx.scope_registry().events("control_spec"))
            eng.close()
        assert all(h.state == "done" for h in hs)
        return {"tokens": sum(len(h.generated) for h in hs),
                "waves": st["decode_pools"],
                "proposed": sp["proposed"], "accepted": sp["accepted"],
                "wall_s": wall, "events": events,
                "k_by_tenant": sp["k_by_tenant"]}, \
            [(h.tokens, np.stack(h.outputs)) for h in hs]

    mixes = (("oracle", "self"), ("adversarial", adversary))
    base = {name: run_one(0, draft) for name, draft in mixes}
    out = {"configs": {}, "n_reqs": n_reqs, "max_new": max_new}
    bit_identical = True
    for k in (1, 2, 4, "auto"):
        tot = {"tokens": 0, "waves": 0, "wasted": 0, "wall_s": 0.0}
        per_mix = {}
        decisions = 0
        for name, draft in mixes:
            doc, outs = run_one(k, draft)
            for (st_, so), (bt, bo) in zip(outs, base[name][1]):
                if st_ != bt or not np.array_equal(so, bo):
                    bit_identical = False
            tot["tokens"] += doc["tokens"]
            tot["waves"] += doc["waves"]
            tot["wasted"] += doc["proposed"] - doc["accepted"]
            tot["wall_s"] += doc["wall_s"]
            decisions += doc["events"]
            per_mix[name] = {
                "accept_rate": round(doc["accepted"]
                                     / max(1, doc["proposed"]), 3),
                "waves": doc["waves"],
                "k_final": doc["k_by_tenant"].get("t")}
        tpw = tot["tokens"] / max(1, tot["waves"])
        wpt = tot["wasted"] / max(1, tot["tokens"])
        out["configs"][f"k{k}"] = {
            "tokens_per_wave": round(tpw, 3),
            "wasted_per_token": round(wpt, 3),
            "score": round(tpw / (1.0 + wpt), 4),
            "tokens_per_s": round(tot["tokens"] / tot["wall_s"], 1),
            "decisions": decisions,
            "mixes": per_mix,
        }
    cfgs = out["configs"]
    best_fixed = max((cfgs[f"k{k}"]["score"] for k in (1, 2, 4)))
    out["best_fixed_score"] = best_fixed
    out["adaptive_score"] = cfgs["kauto"]["score"]
    out["adaptive_ge_best_fixed"] = bool(
        cfgs["kauto"]["score"] >= best_fixed)
    out["bit_identical"] = bit_identical
    return out


def bench_control_suite(m=384, reps=3, fault_delay_us=2000,
                        workers=2, n_reqs=4, max_new=40):
    """ptc-pilot suite (`make bench-control`): the drift soak (incident
    -> drift detection -> recalibrated retune -> pool-boundary hot-swap
    -> recovered throughput, no restart) plus the adaptive-vs-fixed
    spec_k sweep over a mixed oracle/adversarial draft workload."""
    doc = host_provenance(threads=max(workers, 1) + 1)
    doc["soak"] = _control_soak_section(m=m, reps=reps,
                                        fault_delay_us=fault_delay_us)
    doc["spec"] = _control_spec_section(workers=workers, n_reqs=n_reqs,
                                        max_new=max_new)
    return doc


def _arg_after(flag, default):
    if flag in sys.argv:
        return int(sys.argv[sys.argv.index(flag) + 1])
    return default


def _arg_str_after(flag, default):
    if flag in sys.argv:
        return sys.argv[sys.argv.index(flag) + 1]
    return default


def _spotrf_fits(n: int, hbm_bytes: int) -> bool:
    """An fp32 N x N spotrf fits when the matrix plus the device tile
    cache (~2x the matrix, plus slack) fit the device."""
    return 2.2 * n * n * 4 <= hbm_bytes


def spotrf_headline():
    """The headline: spotrf GFLOP/s on the TPU JAX gives this process.
    `--n N --nb NB` pick the size (default: the largest N of 4096..65536
    that fits the chip, NB=512); `--tiled` runs the tile DAG.  No TPU,
    or a device that reports no bytes_limit, is a failure."""
    from parsec_tpu.device.bench_utils import hbm_bytes
    from parsec_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.stderr.write(f"bench.py: the spotrf headline needs a TPU; JAX "
                         f"found {d.platform!r}\n")
        return 1
    hbm = hbm_bytes(d)
    nb = _arg_after("--nb", 512)
    n = _arg_after("--n", 0) or max(
        n for n in (4096, 8192, 16384, 32768, 65536) if _spotrf_fits(n, hbm))
    variant = "tile" if "--tiled" in sys.argv else "panel"
    _, peak = _chip_info()
    gflops = bench_spotrf(n, nb, variant=variant)
    line = {
        "metric": "spotrf_gflops_per_chip",
        "value": round(gflops, 1),
        "unit": "GFLOP/s",
        "vs_baseline": round(gflops / 7000.0, 4),
        "config": {"N": n, "NB": nb, "variant": variant},
        "device": {"platform": d.platform, "kind": d.device_kind,
                   "count": len(devs)},
        "chip_fp32_matmul_gflops": round(peak, 1),
        "frac_of_chip_matmul": round(gflops / peak, 3),
    }
    line.update(_LAST_POTRF_INFO)  # the measured (last) rep's evidence
    print(json.dumps(line))
    return 0


def main():
    if "--dispatch" in sys.argv:
        out = _arg_str_after("--json", None)
        if out:
            # full document (make bench-dispatch -> BENCH_dispatch.json):
            # single-chain + contended percentiles, sched_stats evidence,
            # host provenance
            doc = bench_dispatch_suite(
                tasks=_arg_after("--tasks", 20000),
                mt_tasks=_arg_after("--mt-tasks", 4000),
                reps=_arg_after("--reps", 5),
                workers=_arg_after("--workers", 4),
                lanes=_arg_after("--lanes", 8))
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
            print(_dispatch_json(doc["single_chain"]))
        else:
            print(_dispatch_json())
        return 0
    if "--device" in sys.argv:
        doc = bench_device_suite(
            tiles=_arg_after("--tiles", 96),
            elems=_arg_after("--elems", 32 * 1024),
            batch=_arg_after("--batch", 8),
            reps=_arg_after("--reps", 3),
            gemm_m=_arg_after("--gemm-m", 512),
            gemm_k=_arg_after("--gemm-k", 64),
            gemm_mb=_arg_after("--gemm-mb", 32))
        out = _arg_str_after("--json", None)
        if out:
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
        wp = doc["wave_pipeline"]
        print(json.dumps({
            "metric": "device_h2d_stall_reduction",
            "value": wp["hit_wave_stall_reduction"],
            "unit": "fraction (prefetch-hit wave vs staged baseline)",
            "vs_baseline": (round(wp["hit_wave_stall_reduction"] / 0.8, 3)
                            if wp["hit_wave_stall_reduction"] is not None
                            else None),
            "config": {"tiles": wp["tiles"], "batch": wp["batch"],
                       "ooc_gemm_correct":
                           doc["out_of_core_gemm"]["correct"],
                       "ooc_gemm_spills":
                           doc["out_of_core_gemm"]["spills"]},
        }))
        return 0
    if "--stream" in sys.argv:
        doc = bench_stream_suite(
            size=_arg_after("--size", 4 << 20),
            hops=_arg_after("--hops", 8),
            reps=_arg_after("--reps", 3),
            chunk=_arg_after("--chunk", 1 << 20),
            inflight=_arg_after("--inflight", 4))
        out = _arg_str_after("--json", None)
        if out:
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
        line = {
            "metric": "stream_vs_serialized_latency_ratio",
            "value": doc["stream_vs_serialized_ratio"],
            "unit": "x (lower is better; serialized PR3 serve = 1.0)",
            "vs_baseline": (round(0.6 / doc["stream_vs_serialized_ratio"],
                                  3)
                            if doc["stream_vs_serialized_ratio"] else None),
            "config": {"size_bytes": doc["knobs"]["size_bytes"],
                       "hops": doc["knobs"]["hops"],
                       "rails2_vs_rails1_throughput":
                           doc["rails2_vs_rails1_throughput"],
                       "overlap_fraction":
                           doc["streamed"]["overlap_fraction"]},
        }
        if "caveat" in doc:
            line["caveat"] = doc["caveat"]
        print(json.dumps(line))
        return 0
    if "--collective" in sys.argv:
        sizes_arg = _arg_str_after("--sizes", None)
        sizes = (tuple(int(s) for s in sizes_arg.split(","))
                 if sizes_arg else (64 << 10, 512 << 10, 2 << 20))
        doc = bench_collective_suite(sizes=sizes,
                                     reps=_arg_after("--reps", 3))
        out = _arg_str_after("--json", None)
        if out:
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
        gp = doc["gemm_panel"]
        line = {
            "metric": "coll_vs_chain_reduction_latency_ratio",
            "value": doc["coll_vs_chain_ratio"],
            "unit": "x (lower is better; DAG-dependency chain = 1.0)",
            "vs_baseline": (round(1.0 / doc["coll_vs_chain_ratio"], 3)
                            if doc["coll_vs_chain_ratio"] else None),
            "config": {"sizes": doc["knobs"]["sizes"],
                       "wait_reduction": gp["wait_reduction"],
                       "overlap_fraction_gain":
                           gp["overlap_fraction_gain"],
                       "topology_ops": doc["coll_topology_ops"]},
        }
        if "caveat" in doc:
            line["caveat"] = doc["caveat"]
        print(json.dumps(line))
        return 0
    if "--topo" in sys.argv:
        doc = bench_topo_suite(
            spec=_arg_str_after("--spec", "0,1;2,3"),
            coll_bytes=_arg_after("--coll-bytes", 1 << 20),
            reps=_arg_after("--reps", 3),
            hops=_arg_after("--hops", 8),
            elems=_arg_after("--elems", 1 << 16),
            delay_us=_arg_after("--delay-us", 500))
        out = _arg_str_after("--json", None)
        if out:
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
        rm = doc["remap"]
        print(json.dumps({
            "metric": "topo_remap_dcn_bytes_reduction",
            "value": rm["dcn_reduction"],
            "unit": "fraction of identity-placement DCN bytes removed "
                    "(floor 0.30)",
            "vs_baseline": (round(rm["dcn_reduction"] / 0.30, 3)
                            if rm["dcn_reduction"] is not None else None),
            "config": {"spec": doc["knobs"]["spec"],
                       "delay_us": doc["knobs"]["delay_us"],
                       "predicted_sound": rm["predicted_sound"],
                       "payload_within_25pct":
                           rm["payload_within_25pct"],
                       "allreduce_dcn_ratio_hier_vs_ring":
                           doc["allreduce"]["dcn_ratio_hier_vs_ring"],
                       "bit_identical": doc["bit_identical"]},
        }))
        return 0
    if "--serve" in sys.argv:
        doc = bench_serve_suite(
            n_hi=_arg_after("--hi", 6),
            n_lo=_arg_after("--lo", 18),
            max_new=_arg_after("--max-new", 6),
            workers=_arg_after("--workers", 2))
        out = _arg_str_after("--json", None)
        if out:
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
        line = {
            "metric": "serve_hi_p99_improvement",
            "value": doc["hi_p99_improvement"],
            "unit": "x (hi-tenant p99 control / qos; > 1 = QoS wins)",
            "vs_baseline": doc["hi_p99_improvement"],
            "config": {
                "hi_p99_ms": doc["qos"]["hi"]["p99_ms"],
                "control_hi_p99_ms": doc["control"]["hi"]["p99_ms"],
                "hi_p99_beats_control":
                    doc["qos"]["hi_p99_beats_control"],
                "bit_identical": doc["decode"]["bit_identical"],
                "rejected": doc["admission"]["rejected"],
                "throughput_tok_s": doc["qos"]["throughput_tok_s"],
            },
        }
        if "caveat" in doc:
            line["caveat"] = doc["caveat"]
        print(json.dumps(line))
        return 0
    if "--control" in sys.argv:
        doc = bench_control_suite(
            m=_arg_after("--m", 384),
            reps=_arg_after("--reps", 3),
            fault_delay_us=_arg_after("--delay-us", 2000),
            workers=_arg_after("--workers", 2),
            n_reqs=_arg_after("--reqs", 4),
            max_new=_arg_after("--max-new", 40))
        out = _arg_str_after("--json", None)
        if out:
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
        line = {
            "metric": "control_drift_recovery_ratio",
            "value": doc["soak"]["recovery_ratio"],
            "unit": "fraction of incident-lost throughput recovered "
                    "without restart (>= 0.5 gated)",
            "vs_baseline": doc["soak"]["recovery_ratio"],
            "config": {
                "recovered": doc["soak"]["recovered"],
                "swaps": doc["soak"]["swaps"],
                "persisted": doc["soak"]["persisted"],
                "adaptive_ge_best_fixed":
                    doc["spec"]["adaptive_ge_best_fixed"],
                "adaptive_score": doc["spec"]["adaptive_score"],
                "best_fixed_score": doc["spec"]["best_fixed_score"],
                "bit_identical": doc["spec"]["bit_identical"],
            },
        }
        print(json.dumps(line))
        return 0
    if "--ep" in sys.argv:
        print(_ep_json())
        return 0
    if "--dispatch-mt" in sys.argv:
        mt = bench_dispatch_mt(workers=_arg_after("--workers", 4),
                               lanes=_arg_after("--lanes", 8))
        line = {
            "metric": "task_dispatch_mt_p50",
            "value": mt["p50_us"],
            "unit": "us",
            "vs_baseline": round(5.0 / mt["p50_us"], 3),
            "config": {k: mt[k] for k in
                       ("workers", "workers_requested", "lanes", "tasks",
                        "cpu_count", "oversubscribed")},
            "p99_us": mt["p99_us"],
        }
        if "caveat" in mt:
            line["caveat"] = mt["caveat"]
        print(json.dumps(line))
        return 0
    if "--profov" in sys.argv:
        print(bench_profiling_overhead())
        return 0
    if "--trace" in sys.argv:
        doc = bench_trace_suite(tasks=_arg_after("--tasks", 20000),
                                reps=_arg_after("--reps", 5),
                                ring_bytes=_arg_after("--ring", 1 << 16))
        out = _arg_str_after("--json", None)
        if out:
            with open(out, "w") as f:
                json.dump(doc, f, indent=1)
            sys.stderr.write(f"wrote {out}\n")
        print(json.dumps({
            "metric": "trace_ring_vs_unbounded_level1",
            "value": doc["ring"]["vs_unbounded_level1"],
            "unit": "x (1.0 = no ring overhead; acceptance < 1.1)",
            "vs_baseline": (round(1.1 / doc["ring"]["vs_unbounded_level1"],
                                  3)
                            if doc["ring"]["vs_unbounded_level1"] else None),
            "config": {"tasks": doc["knobs"]["tasks"],
                       "ring_bytes": doc["knobs"]["ring_bytes"],
                       "level1_overhead_ns":
                           doc["overhead_ns_per_task"]["level1"],
                       "ring_dropped": doc["ring"]["dropped_events"]},
        }))
        return 0
    if "--ring" in sys.argv:
        print(bench_ring(S=_arg_after("--s", 8), T=_arg_after("--t", 2048),
                         d=_arg_after("--d", 128)))
        return 0
    return spotrf_headline()


if __name__ == "__main__":
    sys.exit(main())
