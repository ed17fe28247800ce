#!/usr/bin/env python
"""Concurrency stress for the native core under ThreadSanitizer.

Targets the paths that only ever ran effectively single-threaded on this
1-core box: the lock-free Chase-Lev work-stealing deque (lws), the dense
and hashed dependency engines under concurrent release, DTD accessor
chains, and the comm thread's delivery path against worker releases
(colocated 2-rank job in one process).  TSan's happens-before analysis
finds missing synchronization even when the kernel timeslices, so this
is meaningful on one core.

Run:
    make tsan
    PTC_NATIVE_LIB=build/libparsec_core_tsan.so \
    LD_PRELOAD=$(g++ -print-file-name=libtsan.so) \
    TSAN_OPTIONS="suppressions=tools/tsan.supp exitcode=66 \
                  report_thread_leaks=0" \
    timeout 900 python tools/stress_tsan.py

Exit 0 + "stress ok" and no "WARNING: ThreadSanitizer" lines = clean.
(reference practice: the PARANOID/NOISIER debug CI matrix,
.github/workflows/build_cmake.yml:33-34)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import parsec_tpu as pt  # noqa: E402


def ep_burst(sched: str, workers: int, n: int) -> None:
    """Independent tasks: pure produce/steal churn on the deques."""
    with pt.Context(nb_workers=workers, scheduler=sched) as ctx:
        tp = pt.Taskpool(ctx, globals={"NB": n - 1})
        tc = tp.task_class("EP")
        tc.param("k", 0, pt.G("NB"))
        tc.body_noop()
        tp.run()
        tp.wait()
        assert tp.nb_total_tasks == n


def chain_mesh(sched: str, workers: int, nb: int, lanes: int) -> None:
    """`lanes` independent RW chains: concurrent release_deps traffic
    through the dense dependency engine while workers steal."""
    with pt.Context(nb_workers=workers, scheduler=sched) as ctx:
        ctx.register_arena("t", 8)
        tp = pt.Taskpool(ctx, globals={"NB": nb - 1, "L": lanes - 1})
        k, l = pt.L("k"), pt.L("l")
        tc = tp.task_class("Chain")
        tc.param("l", 0, pt.G("L"))
        tc.param("k", 0, pt.G("NB"))
        tc.flow("A", "RW",
                pt.In(None, guard=(k == 0)),
                pt.In(pt.Ref("Chain", l, k - 1, flow="A")),
                pt.Out(pt.Ref("Chain", l, k + 1, flow="A"),
                       guard=(k < pt.G("NB"))),
                arena="t")
        tc.body_noop()
        tp.run()
        tp.wait()
        assert tp.nb_total_tasks == nb * lanes


def dtd_churn(workers: int, tiles: int, rounds: int) -> None:
    """Dynamic insertion racing execution: accessor-chain updates, window
    throttling, freelist reuse."""
    with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
        from parsec_tpu.dsl.dtd import DtdTaskpool
        datas = [ctx.data(i, np.zeros(8, dtype=np.int64))
                 for i in range(tiles)]
        dtp = DtdTaskpool(ctx, window=32)
        tls = [dtp.tile_of(d, owner=0) for d in datas]

        def bump(view):
            view.data(0, dtype=np.int64)[0] += 1

        for _ in range(rounds):
            for t in range(tiles):
                dtp.insert_task(bump, (tls[t], "INOUT"))
        dtp.wait()
        for i, d in enumerate(datas):
            v = np.frombuffer(d.array, dtype=np.int64)[0]
            assert v == rounds, (i, v)
        dtp.destroy()


def colocated_comm(workers: int, nb: int = 64, port: int = 29900,
                   elems: int = 1, env=None) -> None:
    """Two ranks in ONE process (a thread per rank, loopback TCP): the
    comm threads' delivery paths run against both ranks' workers on a
    cross-rank RW chain, all inside one TSan-observed address space.

    elems > 1 (with `env` forcing rendezvous + small chunks + 2 rails)
    drives the wire-v4 socket/session paths — ranged-chunk sessions,
    shared_ptr-pinned zero-copy sendmsg frames, multi-rail striping —
    under TSan's happens-before analysis."""
    import threading

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    errs = []

    def rank_prog(rank):
        try:
            ctx = pt.Context(nb_workers=workers, scheduler="lws")
            ctx.set_rank(rank, 2)
            ctx.comm_init(port)
            with ctx:
                size = 8 * elems
                arr = np.zeros((2, elems), dtype=np.int64)
                ctx.register_linear_collection("A", arr, elem_size=size,
                                               nodes=2, myrank=rank)
                ctx.register_arena("t", size)
                tp = pt.Taskpool(ctx, globals={"NB": nb})
                k = pt.L("k")
                tc = tp.task_class("Task")
                tc.param("k", 0, pt.G("NB"))
                tc.affinity("A", k % 2)
                tc.flow("A", "RW",
                        pt.In(pt.Mem("A", 0), guard=(k == 0)),
                        pt.In(pt.Ref("Task", k - 1, flow="A")),
                        pt.Out(pt.Ref("Task", k + 1, flow="A"),
                               guard=(k < pt.G("NB"))),
                        arena="t")

                def body(view):
                    a = view.data("A", dtype=np.int64, shape=(elems,))
                    assert (a == view["k"]).all()
                    a += 1

                tc.body(body)
                tp.run()
                tp.wait()
                ctx.comm_fence()
                ctx.comm_fini()
        except Exception as e:  # pragma: no cover - stress harness
            errs.append((rank, repr(e)))

    try:
        ts = [threading.Thread(target=rank_prog, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        hung = [t.name for t in ts if t.is_alive()]
        assert not hung, f"deadlocked rank threads: {hung}"
        assert not errs, errs
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def colocated_coll(workers: int, elems: int, port: int, env=None) -> None:
    """Two ranks in ONE process running runtime-native streamed
    collectives (ptc_coll_* task classes, parsec_tpu.comm.coll): the
    reduction/fan-out step deliveries, the coll-stats counters, the
    native bcast-tree switches and (with `env` forcing rendezvous +
    small chunks) the chunked wire sessions under TSan's happens-before
    analysis — every topology exercised."""
    import threading

    from parsec_tpu.comm import coll

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    errs = []

    def rank_prog(rank):
        try:
            ctx = pt.Context(nb_workers=workers, scheduler="lws")
            ctx.set_rank(rank, 2)
            ctx.comm_init(port)
            with ctx:
                alls = [np.arange(elems, dtype=np.float32) + 100.0 * r
                        for r in range(2)]
                total = alls[0] + alls[1]
                for topo in ("ring", "binomial", "star"):
                    got = coll.all_reduce(ctx, alls[rank], topo=topo)
                    assert (got == total).all(), topo
                got = coll.broadcast(ctx, alls[rank].copy(), root=1)
                assert (got == alls[1]).all()
                st = ctx.coll_stats()
                assert st["steps"] > 0, st
                ctx.comm_fence()
                ctx.comm_fini()
        except Exception as e:  # pragma: no cover - stress harness
            errs.append((rank, repr(e)))

    try:
        ts = [threading.Thread(target=rank_prog, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        hung = [t.name for t in ts if t.is_alive()]
        assert not hung, f"deadlocked rank threads: {hung}"
        assert not errs, errs
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def colocated_hier_coll(workers: int, elems: int, port: int,
                        env=None) -> None:
    """ptc-topo: FOUR ranks in one process on a two-island topology
    spec running the hierarchical two-level collectives (intra-island
    binomial reduce onto heads, leaders-only exchange, follower fan-
    out) plus the per-class counter folds — the island-leader step
    deliveries, the -1 route-table deactivations and the classed
    counter reads all under TSan's happens-before analysis."""
    import threading

    from parsec_tpu.comm import coll

    env = dict(env or {})
    env.setdefault("PTC_MCA_comm_topology", "0,1;2,3")
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    nodes = 4
    errs = []

    def rank_prog(rank):
        try:
            ctx = pt.Context(nb_workers=workers, scheduler="lws")
            ctx.set_rank(rank, nodes)
            ctx.comm_init(port)
            with ctx:
                alls = [np.arange(elems, dtype=np.float32) + 100.0 * r
                        for r in range(nodes)]
                total = np.sum(np.stack(alls), axis=0,
                               dtype=np.float32)
                for _ in range(2):
                    got = coll.all_reduce(ctx, alls[rank], topo="hier")
                    assert (got == total).all()
                got = coll.broadcast(ctx, alls[rank].copy(), root=1,
                                     topo="hier")
                assert (got == alls[1]).all()
                st = ctx.coll_stats()
                assert st["by_topo"].get("hier", 0) >= 3, st
                ts = ctx.comm_topo_stats()
                assert ts["n_islands"] == 2, ts
                ctx.comm_fence()
                ctx.comm_fini()
        except Exception as e:  # pragma: no cover - stress harness
            errs.append((rank, repr(e)))

    try:
        ts = [threading.Thread(target=rank_prog, args=(r,))
              for r in range(nodes)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        hung = [t.name for t in ts if t.is_alive()]
        assert not hung, f"deadlocked rank threads: {hung}"
        assert not errs, errs
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def metrics_watchdog_coll(workers: int, elems: int, port: int,
                          env=None) -> None:
    """PR 7 observability paths under TSan: the lock-free metrics hot
    path (per-class EXEC records from CB bodies on every worker, h2d/
    release/comm-wait records), the watchdog thread scanning inflight
    slots + histograms, the Prometheus scrape endpoint serializing
    snapshots, and the fence-time MSG_METRICS rank-wide merge — all
    concurrently with a 2-rank streamed collective over the chunked
    wire."""
    import threading
    import urllib.request

    from parsec_tpu.comm import coll
    from parsec_tpu.profiling.metrics import (MetricsExporter,
                                              MetricsRegistry, Watchdog)

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    errs = []

    def rank_prog(rank):
        try:
            ctx = pt.Context(nb_workers=workers, scheduler="lws")
            ctx.set_rank(rank, 2)
            ctx.comm_init(port)
            with ctx:
                wd = Watchdog(ctx, interval=0.05, floor_s=30.0)
                exporter = MetricsExporter(ctx, 0) if rank == 0 else None
                stop_scrape = threading.Event()

                def scrape():
                    while not stop_scrape.is_set():
                        try:
                            urllib.request.urlopen(
                                f"http://127.0.0.1:{exporter.port}"
                                "/metrics", timeout=5).read()
                        except Exception:
                            pass
                        stop_scrape.wait(0.02)

                scraper = None
                if exporter is not None:
                    scraper = threading.Thread(target=scrape, daemon=True)
                    scraper.start()
                alls = [np.arange(elems, dtype=np.float32) + 100.0 * r
                        for r in range(2)]
                total = alls[0] + alls[1]
                for _ in range(3):
                    got = coll.all_reduce(ctx, alls[rank], topo="ring")
                    assert (got == total).all()
                    ctx.comm_fence()  # fires the MSG_METRICS merge
                reg = MetricsRegistry(ctx)
                assert reg.prometheus_text(merged=(rank == 0))
                assert not wd.events, wd.events  # no false positives
                stop_scrape.set()
                if scraper is not None:
                    scraper.join(timeout=10)
                if exporter is not None:
                    exporter.stop()
                wd.stop()
                ctx.comm_fence()
                ctx.comm_fini()
        except Exception as e:  # pragma: no cover - stress harness
            errs.append((rank, repr(e)))

    try:
        ts = [threading.Thread(target=rank_prog, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        hung = [t.name for t in ts if t.is_alive()]
        assert not hung, f"deadlocked rank threads: {hung}"
        assert not errs, errs
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def prefix_spec_churn(workers: int, reqs_per_thread: int = 6,
                      env=None) -> None:
    """ptc-share churn (PR 14): 2 QoS tenants x 2 submitter threads
    hammer OVERLAPPING prompts through a live InferenceEngine with
    speculative decoding ON and a page pool small enough to force the
    whole shared-prefix life cycle — concurrent `acquire_prefix`
    check-and-reserve against pump-thread retirement (the admission
    race fix), freeze/hit/refcount churn, COW clones, cached-frozen
    eviction and speculative page rollback — while the driver thread
    runs the continuous-batching loop and a reader scrapes the pool
    counters, stats()["serve"] and the tenant-labelled Prometheus text.
    TSan watches the pool lock discipline, the engine/server/scope
    locks and the native QoS-pool churn underneath in one address
    space; a final bit-exactness spot check keeps the stress honest."""
    import threading
    import time

    from parsec_tpu.serve import (InferenceEngine, PagedLM,
                                  PagedLMConfig, TenantConfig)

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        model = PagedLM(PagedLMConfig(vocab=24, d=8, page=4, seed=5))
        rng0 = np.random.RandomState(3)
        common = [list(rng0.randint(0, 24, size=12)) for _ in range(3)]
        with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
            eng = InferenceEngine(
                ctx, model, n_pages=40, max_seqs=8,
                tenants=[TenantConfig("hi", priority=4, weight=3,
                                      max_pools=4, max_queue=128),
                         TenantConfig("lo", max_pools=4,
                                      max_queue=128)],
                spec_k=2)
            reg = ctx.metrics_registry()
            handles, hlock = [], threading.Lock()

            def submitter(tenant, seed):
                rng = np.random.RandomState(seed)
                for _ in range(reqs_per_thread):
                    c = common[rng.randint(len(common))]
                    tail = list(rng.randint(0, 24,
                                            size=rng.randint(0, 3)))
                    h = eng.submit(c[:rng.randint(4, 13)] + tail,
                                   int(rng.randint(2, 5)), tenant)
                    with hlock:
                        handles.append(h)

            stop = threading.Event()

            def reader():
                while not stop.is_set():
                    eng.pool.stats()
                    ctx.stats()["serve"]
                    reg.prometheus_text()
                    stop.wait(0.005)

            subs = [threading.Thread(target=submitter, args=(t, s))
                    for s, t in enumerate(("hi", "lo", "hi", "lo"))]
            rd = threading.Thread(target=reader, daemon=True)
            rd.start()
            for t in subs:
                t.start()
            deadline = time.monotonic() + 300
            while any(t.is_alive() for t in subs) or eng.pending() \
                    or eng._inflight:
                assert time.monotonic() < deadline, "churn deadlocked"
                eng.run(timeout_s=240)
                time.sleep(0.001)
            for t in subs:
                t.join(timeout=60)
            stop.set()
            rd.join(timeout=10)
            st = eng.pool.stats()
            assert st["free"] + st["cached_free"] == st["n_pages"], st
            assert st["prefix_hits"] > 0, st
            with hlock:
                done = [h for h in handles if h.state == "done"]
                assert len(done) == len(handles), \
                    [(h.state, h.tenant) for h in handles]
            for h in done[:4]:
                rt, ro = model.reference_generate(h.prompt,
                                                  h.max_new)
                assert h.tokens == rt
                assert np.array_equal(np.stack(h.outputs), ro)
            eng.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def fleet_churn(workers: int, reqs_per_thread: int = 5,
                env=None) -> None:
    """ptc-route fleet churn (PR 16): TWO replica engines (own contexts,
    one address space) behind one Router; two submitter threads route
    OVERLAPPING shared-prefix prompts through the scored placement path
    (advertise -> digest -> placement_cost) while a migration thread
    hammers content-hash page migration in BOTH directions between the
    live pools — concurrent with each engine's own freeze/acquire/
    eviction churn and the pump-thread retirements underneath — and a
    reader scrapes router.stats() (which walks every replica's
    advertise + pool counters).  TSan watches the router handle-list
    lock, both pool locks under cross-pool export/import, the
    server/scope locks and the native QoS churn in one address space;
    bit-exactness spot checks and exact page accounting on both pools
    keep the stress honest."""
    import threading
    import time

    from parsec_tpu.ops.paged_attention import prefix_page_keys
    from parsec_tpu.serve import (InferenceEngine, PagedLM,
                                  PagedLMConfig, Replica, Router,
                                  TenantConfig)

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        model = PagedLM(PagedLMConfig(vocab=24, d=8, page=4, seed=5))
        rng0 = np.random.RandomState(11)
        common = [list(rng0.randint(0, 24, size=12)) for _ in range(3)]
        ckeys = [prefix_page_keys(model.model_id, c, 4) for c in common]
        ctxs = [pt.Context(nb_workers=workers, scheduler="lws")
                for _ in range(2)]
        try:
            reps = [Replica(InferenceEngine(
                c, model, n_pages=28, max_seqs=6,
                tenants=[TenantConfig("t", max_pools=4,
                                      max_queue=128)],
                name=f"r{i}")) for i, c in enumerate(ctxs)]
            router = Router(reps)
            handles, hlock = [], threading.Lock()

            def submitter(seed):
                rng = np.random.RandomState(seed)
                for _ in range(reqs_per_thread):
                    c = common[rng.randint(len(common))]
                    tail = list(rng.randint(0, 24,
                                            size=rng.randint(0, 3)))
                    fh = router.submit(c + tail,
                                       int(rng.randint(2, 5)), "t")
                    with hlock:
                        handles.append(fh)

            stop = threading.Event()

            def migrator():
                i = 0
                while not stop.is_set():
                    keys = ckeys[i % len(ckeys)]
                    dst = reps[i % 2]
                    src = reps[(i + 1) % 2]
                    router.migrate(keys, dst=dst, src=src)
                    i += 1
                    stop.wait(0.002)

            def reader():
                while not stop.is_set():
                    router.stats()
                    stop.wait(0.005)

            subs = [threading.Thread(target=submitter, args=(s,))
                    for s in (1, 2)]
            aux = [threading.Thread(target=migrator, daemon=True),
                   threading.Thread(target=reader, daemon=True)]
            for t in aux:
                t.start()
            for t in subs:
                t.start()
            deadline = time.monotonic() + 300
            while any(t.is_alive() for t in subs) or router._busy():
                assert time.monotonic() < deadline, "fleet deadlocked"
                router.run(timeout_s=240)
                time.sleep(0.001)
            for t in subs:
                t.join(timeout=60)
            stop.set()
            for t in aux:
                t.join(timeout=10)
            for rep in reps:
                st = rep.pool.stats()
                assert st["free"] + st["cached_free"] == \
                    st["n_pages"], st
            with hlock:
                done = [fh for fh in handles if fh.state == "done"]
                assert len(done) == len(handles), \
                    [fh.state for fh in handles]
            for fh in done[:4]:
                rt, ro = model.reference_generate(fh.prompt,
                                                  fh.max_new)
                assert fh.tokens == rt
                assert np.array_equal(np.stack(fh.outputs), ro)
            router.close()
        finally:
            for c in ctxs:
                c.destroy()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def journal_churn(workers: int, port: int, pools_per_tenant: int = 10,
                  env=None) -> None:
    """ptc-blackbox stress under a 2-rank context: each rank runs a
    Server + a crash-armed Journal with aggressive cadences — record()
    from submitter/pump/worker threads (serve + scope-event hooks)
    racing the cadence thread's drain/fsync/rotation, inventory
    checkpoints snapshotting live scopes + inflight slots + MSG_BLOB
    replication riding the comm engine, crash-header refreshes
    (ptc_crash_update_meta reading the clock/ring atomics) racing
    fence-time clock sync, a FleetView scraping the local server and a
    reader thread on stats()/prometheus (with the ptc_fleet_* family)
    — all in one TSan-observed address space.  The fatal-signal writer
    itself never fires here: its bounded-spin ProfBuf read is
    crash-path-only by design (a deliberate data race TSan must not
    see in healthy runs)."""
    import tempfile
    import threading
    import time

    from parsec_tpu.profiling.blackbox import FleetView, Journal
    from parsec_tpu.serve import Server, TenantConfig

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    errs = []

    def rank_prog(rank, jdir):
        try:
            ctx = pt.Context(nb_workers=workers, scheduler="lws")
            ctx.set_rank(rank, 2)
            ctx.comm_init(port)
            with ctx:
                ctx.register_arena("t", 8)
                ctx.profile_enable(1)
                ctx.profile_ring(1 << 16)
                jr = Journal(ctx, dirpath=jdir, max_bytes=1 << 16,
                             fsync_s=0.02, checkpoint_s=0.05)
                jr.register_inventory(
                    "frozen_page_keys",
                    lambda: [f"page:{rank}:{i}" for i in range(4)])
                srv = Server(ctx, [
                    TenantConfig("hi", priority=4, weight=3,
                                 max_pools=3, max_queue=64),
                    TenantConfig("lo", priority=0, weight=1,
                                 max_pools=3, max_queue=64),
                ])
                fv = FleetView(ctx=ctx, servers=[srv], interval_s=0.01)
                reg = ctx.metrics_registry()

                def mk(priority, weight):
                    tp = ctx.taskpool(globals={"N": 15},
                                      priority=priority, weight=weight)
                    tc = tp.task_class("C")
                    tc.param("k", 0, pt.G("N"))
                    tc.flow("X", "RW",
                            pt.In(None, guard=(pt.L("k") == 0)),
                            pt.In(pt.Ref("C", pt.L("k") - 1, flow="X")),
                            pt.Out(pt.Ref("C", pt.L("k") + 1, flow="X"),
                                   guard=(pt.L("k") < pt.G("N"))),
                            arena="t")
                    tc.body_noop()
                    return tp

                def submitter(tenant):
                    for _ in range(pools_per_tenant):
                        srv.submit(tenant, mk)

                subs = [threading.Thread(target=submitter, args=(t,))
                        for t in ("hi", "lo")]
                stop = threading.Event()

                def reader():
                    while not stop.is_set():
                        ctx.stats()["fleet"]
                        reg.prometheus_text()
                        jr.stats()
                        jr.lost_peers()
                        stop.wait(0.005)

                rd = threading.Thread(target=reader, daemon=True)
                rd.start()
                for t in subs:
                    t.start()
                # fences interleave the MSG_BLOB checkpoints with
                # clock sync + MSG_METRICS merges
                for _ in range(3):
                    ctx.comm_fence()
                    time.sleep(0.05)
                for t in subs:
                    t.join(timeout=120)
                assert srv.drain(timeout=120)
                stop.set()
                rd.join(timeout=10)
                fv.stop()
                srv.close()
                ctx.comm_fence()
                jr.stop()
                st = jr.stats()
                assert st["records"] > 0 and st["checkpoints"] >= 0, st
                ctx.comm_fini()
        except Exception as e:  # pragma: no cover - stress harness
            errs.append((rank, repr(e)))

    try:
        with tempfile.TemporaryDirectory() as td:
            dirs = [os.path.join(td, f"r{r}") for r in range(2)]
            ts = [threading.Thread(target=rank_prog, args=(r, dirs[r]))
                  for r in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            hung = [t.name for t in ts if t.is_alive()]
            assert not hung, f"deadlocked rank threads: {hung}"
            assert not errs, errs
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def control_churn(workers: int, reqs_per_thread: int = 5,
                  env=None) -> None:
    """ptc-pilot churn (PR 19): a live InferenceEngine with ADAPTIVE
    speculation (spec_k='auto') and a feedback Controller bound to it,
    under concurrent fire from every side at once — submitter threads
    on two tenants (per-tenant bandit windows + the page-pressure
    pause/resume gate against a small pool), an observer thread feeding
    drifted makespan ratios and watchdog interrupts (retune evaluation
    + the pool-boundary hot-swap's hold_knobs snapshot/restore racing
    everything), and a scraper hammering ctrl.stats() /
    Context.stats()['control'] / ctrl.poll() (budget-share pushes into
    the pool and admission-pressure pushes into the server) while the
    driver runs the continuous-batching loop (whose _reap also calls
    poll).  TSan watches the controller lock against the engine, pool,
    server and scope locks in one address space; a final bit-exactness
    spot check keeps the adaptive path honest."""
    import threading
    import time

    from parsec_tpu.analysis.control import Controller
    from parsec_tpu.serve import (InferenceEngine, PagedLM,
                                  PagedLMConfig, TenantConfig)

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        model = PagedLM(PagedLMConfig(vocab=24, d=8, page=4, seed=5))
        rng0 = np.random.RandomState(7)
        common = [list(rng0.randint(0, 24, size=10)) for _ in range(3)]
        with pt.Context(nb_workers=workers, scheduler="lws") as ctx:
            ctrl = Controller(ctx, window=4, cooldown=2,
                              drift_ratio=1.25)
            # a small never-run chain as the retune target: evaluate()
            # re-simulates it concurrently with the serving loop
            ctx.register_arena("t", 8)
            tp = pt.Taskpool(ctx, globals={"NB": 199})
            kk = pt.L("k")
            tc = tp.task_class("Task")
            tc.param("k", 0, pt.G("NB"))
            tc.flow("A", "RW",
                    pt.In(None, guard=(kk == 0)),
                    pt.In(pt.Ref("Task", kk - 1, flow="A")),
                    pt.Out(pt.Ref("Task", kk + 1, flow="A"),
                           guard=(kk < pt.G("NB"))),
                    arena="t")
            tc.body_noop()
            ctrl.attach_target(tp, workers=workers)
            eng = InferenceEngine(          # auto-binds to ctx._controller
                ctx, model, n_pages=40, max_seqs=8,
                tenants=[TenantConfig("hi", priority=4, weight=3,
                                      max_pools=4, max_queue=128),
                         TenantConfig("lo", max_pools=4,
                                      max_queue=128)],
                spec_k="auto")
            handles, hlock = [], threading.Lock()

            def submitter(tenant, seed):
                rng = np.random.RandomState(seed)
                for _ in range(reqs_per_thread):
                    c = common[rng.randint(len(common))]
                    h = eng.submit(c[:rng.randint(4, 11)],
                                   int(rng.randint(2, 5)), tenant)
                    with hlock:
                        handles.append(h)

            stop = threading.Event()

            def observer():
                i = 0
                while not stop.is_set():
                    ctrl.observe_pool(2.5 if i % 3 else 0.9)
                    if i % 17 == 11:
                        ctrl.interrupt("stuck_task", key=f"Pool#{i}")
                    i += 1
                    stop.wait(0.002)

            def scraper():
                while not stop.is_set():
                    ctrl.stats()
                    ctx.stats()["control"]
                    ctrl.poll()
                    stop.wait(0.004)

            subs = [threading.Thread(target=submitter, args=(t, s))
                    for s, t in enumerate(("hi", "lo", "hi", "lo"))]
            obs = threading.Thread(target=observer, daemon=True)
            scr = threading.Thread(target=scraper, daemon=True)
            obs.start()
            scr.start()
            for t in subs:
                t.start()
            deadline = time.monotonic() + 300
            while any(t.is_alive() for t in subs) or eng.pending() \
                    or eng._inflight:
                assert time.monotonic() < deadline, "churn deadlocked"
                eng.run(timeout_s=240)
                time.sleep(0.001)
            for t in subs:
                t.join(timeout=60)
            stop.set()
            obs.join(timeout=10)
            scr.join(timeout=10)
            s = ctrl.stats()
            assert s["retunes"] >= 1, s
            assert s["decisions"] >= 1, s
            with hlock:
                done = [h for h in handles if h.state == "done"]
                assert len(done) == len(handles), \
                    [(h.state, h.tenant) for h in handles]
            for h in done[:4]:
                rt, ro = model.reference_generate(h.prompt, h.max_new)
                assert h.tokens == rt
                assert np.array_equal(np.stack(h.outputs), ro)
            eng.close()
            ctrl.stop()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def serve_churn(workers: int, port: int, pools_per_tenant: int = 24,
                env=None) -> None:
    """Serving-runtime stress under a 2-rank context (one process, a
    thread per rank): each rank runs a Server with two QoS tenants and
    TWO concurrent submitter threads hammering admission — per-pool QoS
    lane pushes/pops from every worker, concurrent taskpool
    creation/retirement (pump-thread destroys racing worker
    completions), admission queue/reject churn, and qos_stats reads
    from the stats thread — while comm fences run.  TSan watches the
    new lane machinery, the tp->qos counters, and the grow-only lane
    table publication in one address space.

    ptc-scope (PR 11) rides along: every admitted pool is
    scope-stamped by the Server (tp->scope_id relaxed loads on the
    EXEC span path + the u64 scope word on every cross-rank ACTIVATE),
    tracing level 1 keeps those paths hot, and the reader thread
    scrapes the full surface — Context.stats()["scope"] (registry lock
    vs submitter/pump writers) and the tenant-labelled Prometheus
    text — while pools churn."""
    import threading

    from parsec_tpu.serve import Server, TenantConfig

    env = env or {}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    errs = []

    def rank_prog(rank):
        try:
            ctx = pt.Context(nb_workers=workers, scheduler="lws")
            ctx.set_rank(rank, 2)
            ctx.comm_init(port)
            with ctx:
                ctx.register_arena("t", 8)
                # scope span stamps + SCOPE wire instants stay hot
                # (ring mode bounds the buffers over the pool churn)
                ctx.profile_enable(1)
                ctx.profile_ring(1 << 16)
                srv = Server(ctx, [
                    TenantConfig("hi", priority=4, weight=3,
                                 max_pools=3, max_queue=64,
                                 slo_ms=60_000),
                    TenantConfig("lo", priority=0, weight=1,
                                 max_pools=3, max_queue=64),
                ])
                reg = ctx.metrics_registry()

                def mk(priority, weight):
                    tp = ctx.taskpool(globals={"N": 15},
                                      priority=priority, weight=weight)
                    tc = tp.task_class("C")
                    tc.param("k", 0, pt.G("N"))
                    tc.flow("X", "RW",
                            pt.In(None, guard=(pt.L("k") == 0)),
                            pt.In(pt.Ref("C", pt.L("k") - 1, flow="X")),
                            pt.Out(pt.Ref("C", pt.L("k") + 1, flow="X"),
                                   guard=(pt.L("k") < pt.G("N"))),
                            arena="t")
                    tc.body_noop()
                    return tp

                def submitter(tenant):
                    for _ in range(pools_per_tenant):
                        srv.submit(tenant, mk)

                subs = [threading.Thread(target=submitter, args=(t,))
                        for t in ("hi", "lo")]
                stop = threading.Event()

                def stats_reader():
                    while not stop.is_set():
                        ctx.sched_stats()
                        srv.stats()
                        # scrape surface: scope registry rollup +
                        # tenant-labelled exposition text race the
                        # submitters/pump mutating the same records
                        ctx.stats()["scope"]
                        reg.prometheus_text()
                        stop.wait(0.005)

                rd = threading.Thread(target=stats_reader, daemon=True)
                rd.start()
                for t in subs:
                    t.start()
                for t in subs:
                    t.join(timeout=120)
                assert srv.drain(timeout=120)
                stop.set()
                rd.join(timeout=10)
                st = srv.stats()["totals"]
                assert st["completed"] == 2 * pools_per_tenant, st
                srv.close()
                ctx.comm_fence()
                ctx.comm_fini()
        except Exception as e:  # pragma: no cover - stress harness
            errs.append((rank, repr(e)))

    try:
        ts = [threading.Thread(target=rank_prog, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        hung = [t.name for t in ts if t.is_alive()]
        assert not hung, f"deadlocked rank threads: {hung}"
        assert not errs, errs
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def tp_decode_churn(workers: int, port: int, max_new: int = 5,
                    env=None) -> None:
    """ptc-shard (PR 18): TWO colocated ranks serve ONE tensor-parallel
    PagedLM — qkv/ffn rows and KV pages sharded by head (one PagePool
    per rank), every prefill/decode/verify pool embedding a RefReduce
    ptc_coll_* chain whose slice-granular step deliveries race the
    batched dispatch on the device manager thread and the prefetch
    lane's peeks, all
    over the streamed (rendezvous + chunked, 2-rail) wire.  A reader
    thread per rank concurrently scrapes the head-sharded pool
    counters, stats()["serve"]["tp"] (the coll_wait fold readers) and
    device_stats() while the SPMD step loop and both comm threads
    mutate them in one TSan-observed address space.  A final bitwise
    check against the single-rank reference and the batches>0 /
    coll_pools>0 floors keep the stress honest."""
    import threading
    import time

    from parsec_tpu.serve import InferenceEngine, PagedLM, PagedLMConfig

    env = dict(env or {})
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    errs = []
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9],
               [3, 1, 4, 1, 5]]

    def rank_prog(rank):
        try:
            import jax
            jax.config.update("jax_platforms", "cpu")
            from parsec_tpu.device import TpuDevice

            ctx = pt.Context(nb_workers=workers, scheduler="lws")
            ctx.set_rank(rank, 2)
            ctx.comm_init(port)
            ctx.comm_set_colocated([1 - rank])
            with ctx:
                model = PagedLM(PagedLMConfig(heads=4, qlog=True))
                dev = TpuDevice(ctx)
                try:
                    eng = InferenceEngine(ctx, model, n_pages=64,
                                          max_seqs=4, tp=2, spec_k=2,
                                          dev=dev)
                    stop = threading.Event()

                    def reader():
                        while not stop.is_set():
                            eng.pool.stats()
                            ctx.stats()["serve"]
                            ctx.device_stats()
                            stop.wait(0.003)

                    rd = threading.Thread(target=reader, daemon=True)
                    rd.start()
                    hs = []
                    t0 = time.monotonic()
                    for p in prompts:
                        h = eng.submit(p, max_new)
                        hs.append(h)
                        while h.state == "submitted":
                            assert time.monotonic() - t0 < 240, \
                                "prefill stuck"
                            time.sleep(0.001)
                    while eng.pending() or eng._inflight:
                        assert time.monotonic() - t0 < 240, \
                            "decode stuck"
                        eng.step()
                    stop.set()
                    rd.join(timeout=10)
                    tp_st = eng._tp_stats()
                    assert tp_st["coll_pools"] > 0, tp_st
                    assert dev.stats["batches"] > 0, dev.stats
                    for h in hs:
                        rt, ro = model.reference_generate(h.prompt,
                                                          h.max_new)
                        assert list(h.tokens) == rt
                        for j, o in enumerate(h.outputs):
                            assert np.array_equal(
                                o, model.pre_logits(ro[j]))
                    eng.close()
                finally:
                    dev.stop()
                ctx.comm_fence()
                ctx.comm_fini()
        except Exception as e:  # pragma: no cover - stress harness
            errs.append((rank, repr(e)))

    try:
        ts = [threading.Thread(target=rank_prog, args=(r,))
              for r in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=600)
        hung = [t.name for t in ts if t.is_alive()]
        assert not hung, f"deadlocked rank threads: {hung}"
        assert not errs, errs
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def reshape_churn(workers: int, fanout: int, rounds: int) -> None:
    """Concurrent consumers of the same (copy, [type]) — the memoized
    reshape cache's create/hit race — plus write-back version bumps that
    trigger the stale-entry eviction path against racing readers
    (round-4 machinery: ptc_reshape_get / ReshapeCache)."""
    n = 16
    tile = np.arange(n * n, dtype=np.int32).reshape(n, n)
    with pt.Context(nb_workers=workers) as ctx:
        segs = [(i * n * 4, (i + 1) * 4) for i in range(n)]
        ctx.register_datatype_indexed("LOW", segs)
        ctx.register_datatype_cast("I2L", np.int32, np.int64)
        ctx.register_linear_collection("A", tile, elem_size=tile.nbytes)
        tp = pt.Taskpool(ctx, globals={"NR": rounds - 1, "NF": fanout - 1})
        r = pt.L("r")
        w = tp.task_class("W")
        w.param("r", 0, pt.G("NR"))
        w.flow("A", "RW",
               pt.In(pt.Mem("A", 0), guard=(r == 0)),
               pt.In(pt.Ref("W", r - 1, flow="A")),
               pt.Out(pt.Ref("R", r, pt.Range(0, pt.G("NF")), flow="X")),
               pt.Out(pt.Ref("C", r, pt.Range(0, pt.G("NF")), flow="X")),
               pt.Out(pt.Ref("W", r + 1, flow="A"),
                      guard=(r < pt.G("NR"))),
               pt.Out(pt.Mem("A", 0), ltype="LOW", guard=(r == pt.G("NR"))))

        def wbody(t):
            t.data("A", np.int32)[0] += 1  # version churn per round

        w.body(wbody)
        rd = tp.task_class("R")
        rd.param("r", 0, pt.G("NR"))
        rd.param("f", 0, pt.G("NF"))
        rd.flow("X", "READ", pt.In(pt.Ref("W", r, flow="A"), ltype="LOW"))
        rd.body_noop()
        cc = tp.task_class("C")
        cc.param("r", 0, pt.G("NR"))
        cc.param("f", 0, pt.G("NF"))
        cc.flow("X", "READ", pt.In(pt.Ref("W", r, flow="A"), ltype="I2L"))
        cc.body_noop()
        tp.run()
        tp.wait()


def main():
    reps = int(os.environ.get("STRESS_REPS", "3"))
    for rep in range(reps):
        for sched in ("lws", "lfq", "ll", "lhq"):
            ep_burst(sched, workers=8, n=20000)
            chain_mesh(sched, workers=8, nb=200, lanes=16)
        dtd_churn(workers=8, tiles=8, rounds=100)
        reshape_churn(workers=8, fanout=8, rounds=60)
        # ptc-tune magazine-batch knob (PR 12): non-default batches
        # stress the task/arena refill-spill crossings — a tiny batch
        # maximizes free_lock traffic, a big one maximizes per-spill
        # move size; the knob binds at context create, so each job
        # runs its own contexts under the env
        os.environ["PTC_MCA_runtime_mag_batch"] = "4"
        chain_mesh("lws", workers=8, nb=120, lanes=16)
        os.environ["PTC_MCA_runtime_mag_batch"] = "512"
        chain_mesh("lws", workers=8, nb=120, lanes=16)
        os.environ.pop("PTC_MCA_runtime_mag_batch", None)
        colocated_comm(workers=4, port=29900 + rep)
        # wire-v4 socket/session paths: chunk sessions, zero-copy
        # sendmsg pins, 2-rail striping (16 KiB payloads, 2 KiB chunks)
        colocated_comm(workers=4, nb=24, port=29940 + rep, elems=2048,
                       env={"PTC_MCA_comm_eager_limit": "0",
                            "PTC_MCA_comm_chunk_size": "2048",
                            "PTC_MCA_comm_inflight": "3",
                            "PTC_MCA_comm_rails": "2"})
        # runtime-native collectives over the chunked wire: ptc_coll_*
        # step deliveries + coll counters + per-op bcast-tree switches,
        # every topology, sliced contributions riding 2 KiB chunks
        colocated_coll(workers=4, elems=4096, port=29960 + rep,
                       env={"PTC_MCA_comm_eager_limit": "0",
                            "PTC_MCA_comm_chunk_size": "2048",
                            "PTC_MCA_coll_slice": "4096",
                            "PTC_MCA_comm_rails": "2"})
        # tracing v2 under load: level-2 tracing + flight-recorder RING
        # on a 2-rank job — worker pushes racing the ring's wraparound,
        # comm-thread COMM instants + clock-sync PONG handling on buffer
        # 0, PINS-off trace path (the observability PR's new code under
        # TSan's happens-before analysis)
        colocated_comm(workers=4, nb=48, port=29980 + rep,
                       env={"PTC_MCA_runtime_profile": "1",
                            "PTC_MCA_runtime_trace_ring": "16384"})
        # always-on metrics + watchdog + Prometheus scrape concurrent
        # with a streamed 2-rank collective (PR 7): lock-free histogram
        # records from every worker, inflight-slot scans, snapshot
        # serialization on the scrape thread, fence-time MSG_METRICS
        # merge — TSan watches all of it in one address space
        metrics_watchdog_coll(workers=4, elems=4096, port=30000 + rep,
                              env={"PTC_MCA_comm_eager_limit": "0",
                                   "PTC_MCA_comm_chunk_size": "2048",
                                   "PTC_MCA_comm_rails": "2"})
        # ptc-topo (PR 17): two-island hierarchical collectives, 4
        # colocated ranks — island-leader exchange + follower fan-out
        # step deliveries over the chunked wire + per-class counter
        # folds, one TSan-observed address space
        colocated_hier_coll(workers=2, elems=4096, port=30060 + rep,
                            env={"PTC_MCA_comm_eager_limit": "0",
                                 "PTC_MCA_comm_chunk_size": "2048",
                                 "PTC_MCA_comm_rails": "2"})
        # serving runtime (PR 9): QoS lanes + concurrent pool
        # creation/retirement + admission churn under a 2-rank context
        serve_churn(workers=4, port=30020 + rep)
        # ptc-blackbox (PR 20): crash-armed journal + checkpoint blob
        # replication + FleetView scrapes racing the serve churn
        journal_churn(workers=4, port=30100 + rep)
        # ptc-share (PR 14): shared-prefix COW/eviction + speculative
        # rollback under concurrent submitters, retirement and scrapes
        prefix_spec_churn(workers=4)
        # ptc-route (PR 16): 2 replicas behind the fleet router —
        # scored placement + cross-pool page migration racing both
        # engines' freeze/acquire/eviction churn and stats scrapes
        fleet_churn(workers=4)
        # ptc-pilot (PR 19): feedback controller vs the serving loop —
        # drift observations, interrupts and hot-swaps racing adaptive
        # speculation, budget-share/pressure pushes and stats scrapes
        control_churn(workers=4)
        # ptc-shard (PR 18): 2-rank tensor-parallel decode — embedded
        # RefReduce coll chains + batched waves + prefetch lane under
        # the streamed wire, concurrent stats readers on both ranks
        tp_decode_churn(workers=1, port=30080 + rep,
                        env={"PTC_MCA_comm_eager_limit": "0",
                             "PTC_MCA_comm_chunk_size": "2048",
                             "PTC_MCA_comm_rails": "2"})
        sys.stderr.write(f"rep {rep + 1}/{reps} done\n")
    print("stress ok")


if __name__ == "__main__":
    main()
