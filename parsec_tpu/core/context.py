"""Context: process-wide runtime handle (reference: parsec_context_t,
parsec/runtime.h parsec_init/parsec_context_* — SURVEY.md §2.4/§3.1).

Owns the native context (worker threads, scheduler, registries), Python-side
keep-alives for ctypes callbacks and pinned buffers, and the name→id maps for
data collections and arenas.
"""
from __future__ import annotations

import ctypes as C
from typing import Callable, Dict, List, Optional

import numpy as np

from .. import _native as N


class Data:
    """A named datum with a host copy (reference: parsec_data_t +
    parsec_data_copy_t, parsec/data_internal.h:35-83)."""

    def __init__(self, key: int, array: np.ndarray):
        if not array.flags["C_CONTIGUOUS"]:
            array = np.ascontiguousarray(array)
        self.array = array  # keep-alive
        self.key = key
        self._ptr = N.lib.ptc_data_new(
            key, array.ctypes.data_as(C.c_void_p), array.nbytes)

    @property
    def version(self) -> int:
        if self._ptr is None:
            raise RuntimeError("Data already destroyed")
        return N.lib.ptc_copy_version(N.lib.ptc_data_host_copy(self._ptr))

    def destroy(self):
        if self._ptr:
            N.lib.ptc_data_destroy(self._ptr)
            self._ptr = None


def _numa_vpmap(n: int) -> "List[int]":
    """vp per worker from the NUMA topology: worker w round-robin-binds
    to allowed cpu w % ncpu (bind_worker_thread's order), and its vp is
    that cpu's NUMA node, dense-renumbered.  Flat on hosts without
    sysfs NUMA info (reference: the hwloc-fed vpmap init)."""
    import glob as _glob
    import os as _os
    try:
        cpus = sorted(_os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return [0] * n
    node_of = {}
    for path in _glob.glob("/sys/devices/system/node/node[0-9]*"):
        try:
            node = int(_os.path.basename(path)[4:])
            with open(_os.path.join(path, "cpulist")) as f:
                txt = f.read().strip()
        except (OSError, ValueError):
            continue
        for part in txt.split(","):
            part = part.strip()
            if not part:
                continue
            if "-" in part:
                a, b = part.split("-")
                rng = range(int(a), int(b) + 1)
            else:
                rng = [int(part)]
            for c in rng:
                node_of[c] = node
    if not node_of or not cpus:
        return [0] * n
    nodes_sorted = sorted({node_of.get(c, 0) for c in cpus})
    dense = {nd: i for i, nd in enumerate(nodes_sorted)}
    return [dense[node_of.get(cpus[w % len(cpus)], nodes_sorted[0])]
            for w in range(n)]


class Context:
    def __init__(self, nb_workers: Optional[int] = None,
                 scheduler: Optional[str] = None):
        """Explicit arguments win over the MCA param registry
        (parsec_tpu.utils.params: runtime.nb_workers / runtime.sched /
        runtime.profile) which itself resolves files < env < set()."""
        from ..utils import params as _mca
        if nb_workers is None:
            nb_workers = _mca.get("runtime.nb_workers")
        if scheduler is None:
            scheduler = _mca.get("runtime.sched")
        self._ptr = N.lib.ptc_context_new(nb_workers)
        self.myrank, self.nodes = 0, 1
        if scheduler != "lfq":
            N.lib.ptc_context_set_scheduler(self._ptr, scheduler.encode())
        # the trace level as last set, read by the device managers'
        # span helper without a ctypes call (profiling.trace.ManagerSpans)
        self._prof_level = 0
        if _mca.get("runtime.profile"):
            # same meaning as profile_enable(True): full tracing incl. EDGE
            self.profile_enable(True)
        if _mca.get("runtime.trace_ring"):
            # flight recorder: the native env read in ptc_context_new
            # covers native-only embeddings; re-applying the resolved MCA
            # value keeps file/set() spellings working (sched_bypass
            # pattern)
            N.lib.ptc_profile_set_ring(self._ptr,
                                       _mca.get("runtime.trace_ring"))
        if _mca.get("runtime.trace_dump"):
            N.lib.ptc_flight_set_dump_path(
                self._ptr, _mca.get("runtime.trace_dump").encode())
        self._pins_chain = None
        # monitors/devices lists exist before any hook can install into
        # them (the live monitor registers for teardown at construction)
        self._devices: List = []  # TpuDevice instances (stopped on destroy)
        self._monitors: List = []  # LiveMonitor instances
        if _mca.get("runtime.pins"):
            from ..profiling.pins import enable_from_param
            enable_from_param(self, _mca.get("runtime.pins"))
        if _mca.get("runtime.live"):
            from ..profiling.live import enable_from_param as _live
            _live(self, _mca.get("runtime.live"))
        # always-on metrics (native histograms): re-apply the resolved
        # MCA value over the native env read (sched_bypass pattern)
        N.lib.ptc_metrics_enable(
            self._ptr, 1 if _mca.get("runtime.metrics") else 0)
        N.lib.ptc_metrics_set_release_sample(
            self._ptr, _mca.get("runtime.metrics_relsample"))
        self._metrics_registry = None
        self._metrics_exporter = None
        self._watchdog = None
        if _mca.get("runtime.metrics_port"):
            from ..profiling.metrics import MetricsExporter
            self._metrics_exporter = MetricsExporter(
                self, _mca.get("runtime.metrics_port"))
        if _mca.get("runtime.watchdog"):
            from ..profiling.metrics import enable_from_param as _wd
            self._watchdog = _wd(self, _mca.get("runtime.watchdog"))
        # ptc-blackbox: crash-durable event journal + fleet federation
        self._journal = None
        self._fleetview = None
        self._fence_epoch = 0
        if _mca.get("runtime.journal"):
            from ..profiling.blackbox import enable_from_param as _jr
            self._journal = _jr(self, _mca.get("runtime.journal"))
        if _mca.get("runtime.bind") == "core":
            N.lib.ptc_context_set_binding(self._ptr, 1)
        # same-worker ready-task bypass (sched.bypass / PTC_MCA_sched_bypass)
        N.lib.ptc_context_set_sched_bypass(
            self._ptr, 1 if _mca.get("sched.bypass") else 0)
        # per-pool QoS wave-boundary preemption (sched.qos_preempt)
        N.lib.ptc_context_set_qos_preempt(
            self._ptr, 1 if _mca.get("sched.qos_preempt") else 0)
        # live taskpools (weakrefs): the per-pool QoS rows of
        # stats()["sched"]["pools"] and the serving layer walk these.
        # _tp_lock serializes the walk against Taskpool.destroy — a
        # monitor thread reading qos_stats must never race the native
        # ptc_tp_destroy (serving pools churn constantly)
        import threading as _threading
        self._taskpools: List = []
        self._tp_lock = _threading.Lock()
        self._servers: List = []  # serve.Server instances (stats export)
        if _mca.get("runtime.vpmap") not in ("", "flat"):
            self.set_vpmap(_mca.get("runtime.vpmap"))
        N.lib.ptc_device_set_affinity_skew(
            self._ptr, _mca.get("device.affinity_skew"))
        # per-subsystem debug streams (parsec/utils/debug.c analog)
        for i, name in enumerate(N.DBG_SUBSYSTEMS):
            lvl = _mca.get(f"debug.{name}")
            if lvl:
                N.lib.ptc_context_set_verbose(self._ptr, i, lvl)
        # keep-alives: ctypes callbacks must outlive the native context
        self._expr_cbs: List = []
        self._body_cbs: List = []
        self._coll_cbs: List = []
        self._datas: List[Data] = []
        self._buffers: List[np.ndarray] = []
        self.collections: Dict[str, int] = {}
        # name -> Python collection object (or a shim for native linear
        # collections): rank_of + geometry, read by the static analyses
        # (ptc-verify V009 rank-mapping, ptc-plan residency/comm bounds)
        self.collection_objs: Dict[str, object] = {}
        # ptc-plan pre-run check counters (device.plan_check knob;
        # exported as the stats()["plan"] namespace)
        self._plan_stats: Dict[str, int] = {
            "checks": 0, "over_budget": 0, "predicted_spills": 0,
            "last_peak_bytes": 0, "last_budget_bytes": 0}
        self.arenas: Dict[str, int] = {}
        self.arena_sizes: Dict[str, int] = {}  # name -> elem bytes
        self.datatypes: Dict[str, int] = {}
        # name -> wire payload bytes (None when unknowable, e.g. casts
        # over the whole copy); read by the static verifier's V007
        # dtype/shape rule to tell true layout mismatches from renames
        self.datatype_bytes: Dict[str, Optional[int]] = {}
        self._colocated: set = set()  # ranks sharing this accel client
        self._destroyed = False

    # ------------------------------------------------------------ lifecycle
    def start(self):
        N.lib.ptc_context_start(self._ptr)

    def wait(self):
        N.lib.ptc_context_wait(self._ptr)

    def test(self) -> bool:
        return bool(N.lib.ptc_context_test(self._ptr))

    def destroy(self):
        if not self._destroyed:
            self._destroyed = True
            # teardown counter dump (reference: device_show_statistics)
            try:
                from ..utils.config import params as _mca
                if _mca.get("runtime.stats"):
                    import sys as _sys
                    _sys.stderr.write("ptc stats:\n" + self.stats_dump()
                                      + "\n")
            except Exception:
                pass
            # uninstall the PINS chain while the native context is still
            # alive: teardown reports (print_steals) read native counters
            chain = getattr(self, "_pins_chain", None)
            if chain is not None:
                try:
                    chain.uninstall()
                except Exception:
                    pass
            # ptc-pilot: restore any controller-held knob vector while
            # the registry/env snapshot is still meaningful
            ctrl = getattr(self, "_controller", None)
            if ctrl is not None:
                try:
                    ctrl.stop()
                except Exception:
                    pass
            for attr in ("_fleetview", "_journal", "_watchdog",
                         "_metrics_exporter"):
                obj = getattr(self, attr, None)
                if obj is not None:
                    try:
                        obj.stop()
                    except Exception:
                        pass
            for mon in list(getattr(self, "_monitors", [])):
                try:
                    mon.stop()
                except Exception:
                    pass
            # stop device manager threads first: they block in
            # ptc_device_pop on queues owned by the native context
            for dev in list(getattr(self, "_devices", [])):
                try:
                    dev.stop()
                except Exception:
                    pass
            N.lib.ptc_context_destroy(self._ptr)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.destroy()

    @property
    def nb_workers(self) -> int:
        return N.lib.ptc_context_nb_workers(self._ptr)

    @property
    def scheduler_name(self) -> str:
        """Canonical name of the scheduler module that runs (unknown
        requests fall back to "lfq")."""
        return N.lib.ptc_context_get_scheduler(self._ptr).decode()

    def set_rank(self, myrank: int, nodes: int):
        self.myrank, self.nodes = myrank, nodes
        N.lib.ptc_context_set_rank(self._ptr, myrank, nodes)

    # ------------------------------------------------------------ comm (L4)
    def comm_init(self, base_port: Optional[int] = None):
        """Bring up the distributed control plane: a full-mesh loopback/DCN
        TCP transport carrying dependency activations, memory write-backs,
        DTD completion broadcasts and fences (reference: the MPI-funnelled
        comm engine + remote_dep protocol, parsec/parsec_comm_engine.h,
        parsec/remote_dep.c — SURVEY.md §2.5).  Call set_rank first;
        blocks until all ranks are connected."""
        from ..utils import params as _mca
        if base_port is None:
            base_port = _mca.get("comm.base_port")
        if N.lib.ptc_comm_init(self._ptr, base_port) != 0:
            raise RuntimeError("comm engine init failed")
        topo = _mca.get("comm.bcast_topo")
        if topo != "star":
            self.comm_set_topology(topo)

    def comm_set_colocated(self, ranks):
        """Declare peer ranks whose devices share this process's
        accelerator client (single-controller pod slice; in tests,
        multiple contexts over one jax CPU mesh).  PK_DEVICE payloads
        to/from them are handed off by reference and ride the device
        fabric (ICI) instead of the host transport — the colocated peers
        MUST run a TpuDevice.  Reference seam: comm-engine put/get on
        registered memory, parsec_comm_engine.h:139-160."""
        self._colocated = {int(r) for r in ranks}

    def comm_set_topology(self, topo):
        """Activation-broadcast propagation topology: "star" (direct
        per-rank sends), "chain" (pipeline along the ring), "binomial"
        (log-depth tree).  Reference: runtime_comm_coll_bcast,
        parsec/remote_dep.c:39-47."""
        names = {"star": 0, "chain": 1, "binomial": 2}
        if isinstance(topo, str):
            if topo not in names:
                raise ValueError(
                    f"unknown broadcast topology {topo!r} "
                    f"(comm.bcast_topo): expected one of {sorted(names)}")
            t = names[topo]
        else:
            t = int(topo)
            if t not in (0, 1, 2):
                raise ValueError(
                    f"unknown broadcast topology {topo!r}: expected 0 (star),"
                    " 1 (chain), 2 (binomial)")
        N.lib.ptc_comm_set_topology(self._ptr, t)

    def comm_fence(self):
        """Flush + all-to-all fence: on return, every message sent by any
        rank before its fence has been applied everywhere.  Raises when a
        peer's connection died (fail-fast: a crashed rank can no longer
        hang the survivors, VERDICT r2 weak #5) or on timeout when
        PTC_MCA_comm_fence_timeout_s is set (default infinite — a slow
        peer is not a dead peer)."""
        rc = N.lib.ptc_comm_fence(self._ptr)
        jr = getattr(self, "_journal", None)
        if rc == 0:
            # fence-epoch counter: journal records bracket the run into
            # globally-quiesced intervals (the postmortem's time ruler)
            self._fence_epoch = getattr(self, "_fence_epoch", 0) + 1
            if jr is not None:
                jr.record("fence", epoch=self._fence_epoch)
        elif jr is not None:
            jr.record("fence", epoch=getattr(self, "_fence_epoch", 0),
                      error="peer_lost" if rc == -2 else "timeout")
        if rc == -2:
            raise RuntimeError("comm fence failed: peer lost")
        if rc != 0:
            raise RuntimeError("comm fence timed out")

    def comm_quiesce(self, tp=None):
        """Counting termination detection (reference: the fourcounter
        global-TD module, mca/termdet/fourcounter/termdet_fourcounter.h —
        re-designed as a symmetric double wave of application-message
        counters).  Blocks until the system is globally quiescent: every
        rank idle (for `tp`, its task count zero; context-wide otherwise)
        with no application message in flight.  Usable by DSLs that
        cannot count tasks a priori (DTD).  Raises like comm_fence."""
        tptr = tp._ptr if tp is not None else None
        rc = N.lib.ptc_comm_quiesce(self._ptr, tptr)
        if rc == -2:
            raise RuntimeError("termdet quiesce failed: peer lost")
        if rc != 0:
            raise RuntimeError("termdet quiesce timed out")

    def comm_fini(self):
        N.lib.ptc_comm_fini(self._ptr)

    @property
    def comm_enabled(self) -> bool:
        return bool(N.lib.ptc_comm_enabled(self._ptr))

    def worker_stats(self) -> list:
        """Selected-task count per worker thread: scheduler pops, the
        PAPI-SDE TASKS_SCHEDULED analog (parsec/scheduling.c:319-323).
        AGAIN re-schedules count once per pass; ASYNC device chores count
        at dispatch (their execution happens on the device manager)."""
        cap = max(1, self.nb_workers)
        buf = (C.c_int64 * cap)()
        n = N.lib.ptc_worker_stats(self._ptr, buf, cap)
        return [buf[i] for i in range(n)]

    def worker_steals(self) -> list:
        """Per-worker steal counts: selects served from a VICTIM's queue
        (the mca/pins/print_steals data; zero under global-queue
        schedulers, which have nothing to steal)."""
        cap = max(1, self.nb_workers)
        buf = (C.c_int64 * cap)()
        n = N.lib.ptc_worker_steals(self._ptr, buf, cap)
        return [buf[i] for i in range(n)]

    def tasks_in_flight(self) -> int:
        """Tasks handed to the scheduler and not yet back from their
        execution (routed to a device queue, completed, requeued or
        failed).  A task's successors are counted before it leaves, so
        0 means the workers hold no release chain."""
        return N.lib.ptc_tasks_in_flight(self._ptr)

    def sched_stats(self) -> dict:
        """Dispatch fast-path counters: same-worker bypass hits (tasks
        that skipped the schedule/select round trip), task/arena
        freelist magazine hit rates, batched-insert accounting, and the
        lock-free inject queue's traffic — plus the per-worker steal
        and selected-task vectors (the print_steals data, readable from
        Python at last instead of only at PINS teardown).  QoS rows:
        qos_selects/qos_preempts aggregate the lws lane traffic, and
        `pools` lists every live QoS-armed taskpool's per-pool counters
        (the serving runtime's scheduler evidence)."""
        buf = (C.c_int64 * 12)()
        n = N.lib.ptc_sched_stats(self._ptr, buf, 12)
        v = [buf[i] for i in range(n)] + [0] * (12 - n)
        return {
            "bypass_hits": v[0],
            "bypass_enabled": bool(v[1]),
            "freelist_hits": v[2],
            "freelist_misses": v[3],
            "arena_hits": v[4],
            "arena_misses": v[5],
            "insert_batches": v[6],
            "insert_batched_tasks": v[7],
            "inject_pushes": v[8],
            "inject_pops": v[9],
            "qos_selects": v[10],
            "qos_preempts": v[11],
            "qos_preempt_enabled": bool(
                N.lib.ptc_context_get_qos_preempt(self._ptr)),
            "pools": self._qos_pool_rows(),
            "steals": self.worker_steals(),
            "executed": self.worker_stats(),
        }

    # ------------------------------------------------------- QoS taskpools
    def taskpool(self, globals: Optional[Dict[str, int]] = None,
                 priority: Optional[int] = None,
                 weight: Optional[int] = None,
                 scope: Optional[int] = None):
        """Create a Taskpool on this context.  `priority`/`weight` arm
        per-pool QoS (the serving runtime's tenant knobs): under the lws
        scheduler a higher-priority pool's ready tasks win every select
        boundary (wave-boundary preemption; negative priorities are
        background, served only when the default path is dry), and
        weight stride-shares one priority tier.  Per-pool counters
        export through stats()["sched"]["pools"].  `scope` stamps a
        request-scope id for per-request observability (see
        profiling/scope.py)."""
        from .taskpool import Taskpool
        return Taskpool(self, globals=globals, priority=priority,
                        weight=weight, scope=scope)

    def _ensure_tp_tracking(self):
        if getattr(self, "_taskpools", None) is None:
            import threading
            self._taskpools = []
            self._tp_lock = threading.Lock()

    def _track_taskpool(self, tp):
        """STRONG reference until Taskpool.destroy().  Strong on
        purpose: a fire-and-forget serving pool (Server.submit caller
        dropping its ticket) otherwise becomes an unreferenced
        {Taskpool, ctypes-thunk, callback} CYCLE that the cyclic GC
        collects while the NATIVE pool is still running — the freed
        libffi trampoline is then called by tp_mark_complete (observed:
        heap-scrambled ctypes callbacks, then SEGV, under serve churn).
        The native pool's lifetime anchors the wrapper's."""
        self._ensure_tp_tracking()
        with self._tp_lock:
            self._taskpools.append(tp)

    def _untrack_taskpool_locked(self, tp):
        """Caller holds _tp_lock (Taskpool.destroy)."""
        self._taskpools = [p for p in self._taskpools if p is not tp]

    def live_taskpools(self) -> list:
        """Live (not destroyed) Taskpool objects created on this
        context, oldest first."""
        self._ensure_tp_tracking()
        with self._tp_lock:
            return [tp for tp in self._taskpools if not tp._destroyed]

    def _qos_pool_rows(self) -> list:
        """Per-pool QoS counter rows.  The whole walk holds _tp_lock so
        a concurrently-retiring pool (Server pump / engine reap calling
        Taskpool.destroy) can never be freed mid-read."""
        self._ensure_tp_tracking()
        rows = []
        with self._tp_lock:
            for tp in self._taskpools:
                if tp._destroyed:
                    continue
                st = tp.qos_stats()
                if st is not None:
                    st["id"] = tp.tp_id
                    rows.append(st)
        return rows

    def rusage(self) -> dict:
        """Process resource usage (the reference's per-EU rusage dumps,
        parsec/scheduling.c:45-86 — user/sys time, maxrss, context
        switches; process-wide here, workers being threads)."""
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "utime_s": round(ru.ru_utime, 3),
            "stime_s": round(ru.ru_stime, 3),
            "maxrss_kb": ru.ru_maxrss,
            "vol_ctx_switches": ru.ru_nvcsw,
            "invol_ctx_switches": ru.ru_nivcsw,
            "minor_faults": ru.ru_minflt,
            "major_faults": ru.ru_majflt,
        }

    def stats_dump(self) -> str:
        """Human-readable counter dump (the --mca device_show_statistics /
        dump_and_reset analog, parsec/mca/device/device.h:224)."""
        lines = [f"workers (selected tasks): {self.worker_stats()}"]
        steals = self.worker_steals()
        if any(steals):
            lines.append(f"worker steals: {steals}")
        ss = self.sched_stats()
        if ss["bypass_hits"] or ss["freelist_hits"] or ss["inject_pushes"]:
            lines.append(
                "dispatch: bypass=%d freelist=%d/%d arena=%d/%d inject=%d"
                % (ss["bypass_hits"], ss["freelist_hits"],
                   ss["freelist_hits"] + ss["freelist_misses"],
                   ss["arena_hits"], ss["arena_hits"] + ss["arena_misses"],
                   ss["inject_pushes"]))
        bindings = [self.worker_binding(w) for w in range(self.nb_workers)]
        if any(b >= 0 for b in bindings):
            lines.append(f"worker cpu bindings: {bindings}")
        for i, dev in enumerate(self._devices):
            qid = getattr(dev, "qid", None)
            if qid is not None:
                lines.append(f"device[{i}] queue={qid} "
                             f"depth={self.device_queue_depth(qid)}")
            if hasattr(dev, "info"):
                lines.append(f"device[{i}] info: {dev.info()}")
        if self.comm_enabled:
            lines.append(f"comm: {self.comm_stats()}")
        lines.append(f"rusage: {self.rusage()}")
        return "\n".join(lines)

    def comm_stats(self) -> dict:
        buf = (C.c_int64 * 4)()
        N.lib.ptc_comm_stats(self._ptr, buf)
        return {"msgs_sent": buf[0], "msgs_recv": buf[1],
                "bytes_sent": buf[2], "bytes_recv": buf[3]}

    def comm_rdv_stats(self) -> dict:
        """Rendezvous-protocol counters.  After a fence, registered_bytes
        and pending_pulls must both be 0 (bounded comm memory)."""
        buf = (C.c_int64 * 4)()
        N.lib.ptc_comm_rdv_stats(self._ptr, buf)
        return {"gets_sent": buf[0], "gets_served": buf[1],
                "registered_bytes": buf[2], "pending_pulls": buf[3]}

    def comm_tuning(self) -> dict:
        """Effective transfer-path tuning + chunk-protocol counters:
        the eager/rendezvous threshold actually in force (fixed, or
        derived by the adaptive calibration from measured RTT and
        memcpy rate), the chunk/window knobs, and how many pipelined
        chunks moved.  The transfer-economics harness embeds this in
        its JSON so every report names the knobs it ran under."""
        buf = (C.c_int64 * 8)()
        N.lib.ptc_comm_tuning(self._ptr, buf)
        out = {"eager_limit": buf[0], "chunk_size": buf[1],
               "inflight": buf[2], "rtt_ns": buf[3],
               "memcpy_bps": buf[4], "chunks_sent": buf[5],
               "chunks_recv": buf[6], "eager_adaptive": bool(buf[7])}
        out["stream"] = self.comm_stream_stats()
        return out

    def comm_stream_stats(self) -> dict:
        """Cross-rank streaming-pipeline counters (wire v4): progressive-
        serve sessions, ranged GETs parked above the d2h watermark, the
        per-hop span sums (d2h window, wire window, their overlap — the
        serialized PR3 serve has overlap 0 by construction), peer-loss
        session/pin reaps, and the rail count.  overlap_fraction is the
        share of producer d2h time the wire was already moving under —
        the tentpole's evidence number."""
        buf = (C.c_int64 * 8)()
        N.lib.ptc_comm_stream_stats(self._ptr, buf)
        d2h = buf[3]
        return {"sessions": buf[0], "parked_gets": buf[1],
                "overlap_ns": buf[2], "d2h_ns": d2h, "wire_ns": buf[4],
                "reaps": buf[5], "rails": buf[6],
                "stream_enabled": bool(buf[7]),
                "overlap_fraction":
                    round(buf[2] / d2h, 4) if d2h > 0 else None}

    def comm_peer_stats(self) -> list:
        """Per-peer wire counters (ptc-topo): one dict per peer rank
        with bytes/msgs sent+received, parked streaming GETs, and the
        min probed RTT to that peer (0 until a probe ran).  Empty when
        comm is off."""
        cap = max(1, self.nodes)
        buf = (C.c_int64 * (cap * 6))()
        n = N.lib.ptc_comm_peer_stats(self._ptr, buf, cap)
        out = []
        for r in range(n):
            b = buf[r * 6:r * 6 + 6]
            out.append({"bytes_sent": int(b[0]), "bytes_recv": int(b[1]),
                        "msgs_sent": int(b[2]), "msgs_recv": int(b[3]),
                        "parked_gets": int(b[4]), "rtt_ns": int(b[5])})
        return out

    def comm_probe_rtts(self) -> int:
        """PING every peer and wait (<= 2 s) for per-peer min RTTs —
        the link-class auto-detect input (TopologyModel.from_rtts).
        Returns the number of peers with a measured RTT."""
        return int(N.lib.ptc_comm_probe_rtts(self._ptr))

    def comm_topo_stats(self) -> dict:
        """Per-link-class wire counters (ptc-topo): the per-peer native
        counters folded through the TopologyModel in force, plus the
        detected class matrix.  Schema is stable when comm is off (all
        classes present, zeroed; matrix empty) so the unified-stats
        golden schema holds across single- and multi-rank runs."""
        from ..comm.topology import LINK_CLASSES, default_topology
        keys = ("bytes_sent", "bytes_recv", "msgs_sent", "msgs_recv",
                "parked_gets")
        classes = {c: {k: 0 for k in keys} for c in LINK_CLASSES}
        peers = self.comm_peer_stats()
        rtts = {r: p["rtt_ns"] for r, p in enumerate(peers)
                if p["rtt_ns"] > 0}
        topo = default_topology(self.nodes, rtts_ns=rtts or None,
                                my_rank=self.myrank)
        for r, p in enumerate(peers):
            cls = topo.class_of(self.myrank, r)
            row = classes[cls]
            for k in keys:
                row[k] += p[k]
        return {"classes": classes,
                "matrix": topo.matrix() if peers else [],
                "n_islands": topo.n_islands,
                "source": topo.source}

    def coll_stats(self) -> dict:
        """Runtime-native collective counters (the ptc_coll_* task-class
        family built by parsec_tpu.comm.coll): native step/frame/byte
        counters plus the Python builder's op-level records (ops built,
        topology chosen per op — the economics selector's decisions are
        auditable, not implicit)."""
        buf = (C.c_int64 * 6)()
        N.lib.ptc_coll_stats(self._ptr, buf)
        py = getattr(self, "_coll_py_stats", None) or {
            "ops": 0, "by_kind": {}, "by_topo": {}}
        return {
            "steps": buf[0],
            "send_msgs": buf[1], "send_bytes": buf[2],
            "recv_msgs": buf[3], "recv_bytes": buf[4],
            "ops": py["ops"],
            "by_kind": dict(py["by_kind"]),
            "by_topo": dict(py["by_topo"]),
        }

    def stats(self) -> dict:
        """Unified counter snapshot: every stats surface this context
        exports, merged under one namespaced dict — ONE call for the
        serving/observability layers instead of four, taken at a single
        point in time.
          sched   -> sched_stats() (dispatch fast paths, steals, ...)
          device  -> device_stats() (prefetch/spill/h2d, per-device info)
          comm    -> engine/rdv/tuning/stream/topo counter groups (empty
                     sub-dicts stay present when comm is off, so the
                     schema is stable across single- and multi-rank
                     runs; topo is the ptc-topo per-link-class split)
          coll    -> coll_stats() (runtime-native collective steps,
                     frames/bytes, per-op topology decisions)
          trace   -> tracing health: level, ring/drop state of the
                     flight recorder, and the clock-sync estimate
          metrics -> always-on histogram subsystem health: enabled
                     flag, interned class count, watchdog status
          serve   -> serving front door (parsec_tpu.serve.Server):
                     admission/queue/reject counters per tenant;
                     {"enabled": False} when no Server is attached
          plan    -> ptc-plan pre-run checks (device.plan_check knob):
                     check/over-budget counters and the last predicted
                     peak vs budget
          scope   -> request-scoped observability (profiling/scope.py):
                     per-tenant SLO rollups + plan-vs-measured
                     conformance ratios; {"enabled": False} when no
                     ScopeRegistry is attached
          control -> ptc-pilot feedback controller (analysis/control.py):
                     drift window, retune/swap counters, last swap,
                     per-tenant adaptive spec_k and budget shares;
                     {"enabled": False} when no Controller is attached
          fleet   -> ptc-blackbox fleet federation (profiling/blackbox
                     FleetView): per-replica occupancy/health rows +
                     fleet-merged per-tenant SLO burn and aggregate
                     tokens/s; {"enabled": False} when no FleetView is
                     attached
        """
        from ..utils import params as _plan_mca
        tuning = self.comm_tuning()
        wd = getattr(self, "_watchdog", None)
        exp = getattr(self, "_metrics_exporter", None)
        servers = [s for s in getattr(self, "_servers", [])]
        serve_ns = {"enabled": False}
        if servers:
            # one Server per context in practice; the last attached wins
            serve_ns = dict(servers[-1].stats())
            serve_ns["enabled"] = True
        return {
            "sched": self.sched_stats(),
            "serve": serve_ns,
            "device": self.device_stats(),
            "comm": {
                "enabled": self.comm_enabled,
                "engine": self.comm_stats(),
                "rdv": self.comm_rdv_stats(),
                "tuning": tuning,
                # same snapshot as tuning["stream"], surfaced at the top
                # level too — one native read, two access paths, no skew
                "stream": tuning["stream"],
                # ptc-topo: per-link-class byte/msg split + class matrix
                "topo": self.comm_topo_stats(),
            },
            "coll": self.coll_stats(),
            "trace": {
                "level": self.profile_level(),
                "ring_bytes": self.profile_ring(),
                "dropped_events": self.profile_dropped(),
                "clock": self.comm_clock(),
            },
            "metrics": {
                "enabled": self.metrics_enabled,
                "classes": N.lib.ptc_metrics_nclasses(self._ptr),
                "exporter_port": exp.port if exp is not None else 0,
                "watchdog": wd.status() if wd is not None else None,
            },
            "plan": dict(
                enabled=_plan_mca.get("device.plan_check") != "off",
                **getattr(self, "_plan_stats", {})),
            "scope": (self._scope_registry.stats()
                      if getattr(self, "_scope_registry", None) is not None
                      else {"enabled": False}),
            "control": (self._controller.stats()
                        if getattr(self, "_controller", None) is not None
                        else {"enabled": False}),
            "fleet": (self._fleetview.snapshot()
                      if getattr(self, "_fleetview", None) is not None
                      else {"enabled": False}),
        }

    def scope_registry(self, create: bool = True):
        """The request-scope observability registry (one per context;
        profiling/scope.py).  Allocates scope ids, tracks per-request
        lifecycles + per-tenant SLO histograms, and records
        plan-vs-measured conformance at pool retirement.  The serve
        stack attaches one automatically; create=False just peeks."""
        reg = getattr(self, "_scope_registry", None)
        if reg is None and create:
            from ..profiling.scope import ScopeRegistry
            reg = self._scope_registry = ScopeRegistry(self)
        return reg

    def controller(self, create: bool = True, **kwargs):
        """The ptc-pilot feedback controller (one per context;
        analysis/control.py).  Consumes the scope registry's
        conformance observations at pool boundaries, retunes knob
        vectors on drift, and drives adaptive speculation depth and
        tenant cache budgets.  create=False just peeks; kwargs
        (clock=, drift_ratio=, window=, ...) apply only on creation."""
        ctrl = getattr(self, "_controller", None)
        if ctrl is None and create:
            from ..analysis.control import Controller
            ctrl = Controller(self, **kwargs)
        return ctrl

    # ------------------------------------------------------------ registries
    def register_expr_cb(self, fn: Callable) -> int:
        cb = N.EXPR_CB_T(fn)
        self._expr_cbs.append(cb)
        return N.lib.ptc_register_expr_cb(self._ptr, cb, None)

    def register_body_cb(self, fn: Callable) -> int:
        cb = N.BODY_CB_T(fn)
        self._body_cbs.append(cb)
        return N.lib.ptc_register_body(self._ptr, cb, None)

    def data(self, key: int, array: np.ndarray) -> Data:
        d = Data(key, array)
        self._datas.append(d)
        return d

    def register_linear_collection(self, name: str, array: np.ndarray,
                                   elem_size: Optional[int] = None,
                                   nodes: int = 1, myrank: int = 0) -> int:
        """Built-in 1-D host collection: key k → base + k*elem_size,
        rank_of(k) = k % nodes.  Evaluated fully natively (no GIL on the
        dependency path) — the bench-path equivalent of a user collection."""
        if not array.flags["C_CONTIGUOUS"]:
            raise ValueError("linear collection array must be C-contiguous")
        if elem_size is None:
            elem_size = array.itemsize * (array.size // max(1, array.shape[0]))
        nb = array.nbytes // elem_size
        self._buffers.append(array)
        dc = N.lib.ptc_register_linear_collection(
            self._ptr, nodes, myrank, array.ctypes.data_as(C.c_void_p),
            nb, elem_size)
        self.collections[name] = dc
        from ..analysis.flowgraph import LinearCollectionShim
        self.collection_objs[name] = LinearCollectionShim(nodes, elem_size)
        return dc

    def register_collection(self, name: str, coll) -> int:
        """Register a Python data collection (duck-typed vtable: rank_of(*idx)
        → int, data_of(*idx) → Data).  Reference analog:
        parsec_data_collection_t (parsec/include/parsec/data_distribution.h).
        """
        def _rank_of(user, idx, n):
            return coll.rank_of(*[idx[i] for i in range(n)])

        def _data_of(user, idx, n):
            d = coll.data_of(*[idx[i] for i in range(n)])
            return d._ptr if d is not None else None

        rcb = N.RANK_OF_CB_T(_rank_of)
        dcb = N.DATA_OF_CB_T(_data_of)
        self._coll_cbs.append((rcb, dcb, coll))
        dc = N.lib.ptc_register_collection(
            self._ptr, getattr(coll, "nodes", 1), getattr(coll, "myrank", 0),
            rcb, dcb, None)
        self.collections[name] = dc
        self.collection_objs[name] = coll
        return dc

    def register_arena(self, name: str, elem_size: int) -> int:
        aid = N.lib.ptc_register_arena(self._ptr, elem_size)
        self.arenas[name] = aid
        self.arena_sizes[name] = elem_size
        return aid

    def set_vpmap(self, spec) -> List[int]:
        """Virtual-process map (reference: parsec/vpmap.c): a vp id per
        worker, before the context starts.  `spec` is a list of ints,
        'numa' (derive from the NUMA node each worker's round-robin
        binding cpu belongs to), or a comma-separated string.  Returns
        the applied list.  Hierarchical schedulers (lhq) steal within a
        vp before crossing vps."""
        n = N.lib.ptc_context_nb_workers(self._ptr)
        if isinstance(spec, (list, tuple)):
            vps = [int(x) for x in spec]
        elif spec == "numa":
            vps = _numa_vpmap(n)
        else:
            vps = [int(x) for x in str(spec).split(",") if x.strip()]
        if not vps:
            vps = [0] * n
        if len(vps) < n:  # short specs repeat (vpmap file semantics)
            vps = (vps * (n // len(vps) + 1))[:n]
        vps = vps[:n]
        arr = (C.c_int32 * n)(*vps)
        if N.lib.ptc_context_set_vpmap(self._ptr, arr, n) != 0:
            raise RuntimeError(
                "set_vpmap: context already started — the scheduler was "
                "installed with the previous map")
        return vps

    def set_rank_map(self, perm) -> None:
        """Install (or clear, with None/empty) the ptc-topo rank remap:
        a permutation applied to every collection rank_of result, so
        task affinity, successor placement and mem owners relabel
        consistently — plan.remap_ranks() computes one that minimizes
        predicted DCN-crossing bytes.  MUST be identical on every rank
        (SPMD placement), and set between taskpool build and run —
        rank_of is evaluated lazily at pool startup."""
        if not perm:
            N.lib.ptc_context_set_rank_map(self._ptr, None, 0)
            return
        perm = [int(x) for x in perm]
        if sorted(perm) != list(range(len(perm))):
            raise ValueError(f"rank map must be a permutation of "
                             f"0..{len(perm) - 1}, got {perm}")
        arr = (C.c_int32 * len(perm))(*perm)
        N.lib.ptc_context_set_rank_map(self._ptr, arr, len(perm))

    def sched_victim_order(self, worker: int, cap: int = 64):
        """A hierarchical scheduler's computed steal order for `worker`
        (None for flat modules) — test/debug probe."""
        out = (C.c_int32 * cap)()
        k = N.lib.ptc_sched_victim_order(self._ptr, worker, out, cap)
        return None if k < 0 else list(out[:k])

    def worker_binding(self, worker: int) -> int:
        """CPU the worker thread is pinned to (runtime.bind=core), or -1
        when unbound / not yet started (reference: parsec_hwloc.c)."""
        return N.lib.ptc_worker_binding(self._ptr, worker)

    def register_datatype(self, name: str, elem_bytes: int, count: int,
                          stride_bytes: Optional[int] = None) -> int:
        """Wire datatype: `count` blocks of `elem_bytes` spaced
        `stride_bytes` apart (default contiguous).  Attach per dep
        (In/Out dtype= or JDF `[type = name]`): OUT deps pack to
        contiguous wire bytes, IN deps scatter into the consumer layout
        — the MPI-datatype layer analog (reference:
        parsec/datatype/datatype_mpi.c; SURVEY §2.5 datatype row).
        Register in the same order on every rank (SPMD ids)."""
        if stride_bytes is None:
            stride_bytes = elem_bytes
        did = N.lib.ptc_register_datatype(self._ptr, elem_bytes, count,
                                          stride_bytes)
        if did < 0:
            raise ValueError(
                f"bad datatype {name!r}: elem={elem_bytes} count={count} "
                f"stride={stride_bytes} (need elem>0, count>0, "
                "stride>=elem)")
        self.datatypes[name] = did
        self.datatype_bytes[name] = elem_bytes * count
        return did

    def register_datatype_indexed(self, name: str, segments) -> int:
        """Indexed datatype: explicit (offset_bytes, len_bytes) segments —
        the MPI_Type_indexed analog (expresses triangles etc.).  Usable as
        a wire type (pack/scatter the segments) or as a dep's LOCAL
        reshape type (In/Out ltype= or JDF `[type = name]`): the dep's
        data is routed through a new datacopy holding only the selected
        bytes, memoized per (source copy, type) — the reference's
        datacopy-future reshape chain (parsec/parsec_reshape.c,
        parsec/utils/parsec_datacopy_future.c)."""
        n = len(segments)
        offs = (C.c_int64 * n)(*[int(o) for o, _ in segments])
        lens = (C.c_int64 * n)(*[int(ln) for _, ln in segments])
        did = N.lib.ptc_register_datatype_indexed(self._ptr, offs, lens, n)
        if did < 0:
            raise ValueError(
                f"bad indexed datatype {name!r}: need >=1 segment, "
                "offsets >= 0, lens > 0")
        self.datatypes[name] = did
        self.datatype_bytes[name] = sum(int(ln) for _, ln in segments)
        return did

    def register_datatype_cast(self, name: str, from_dtype, to_dtype,
                               count: int = -1) -> int:
        """Element-cast datatype: contiguous `count` elements (-1 = the
        whole copy) converted from_dtype -> to_dtype.  As a local reshape
        type this is the arbitrary type->type promise of the reference's
        reshape machinery; on a Mem write-back dep the conversion
        reverses (reference: parsec_reshape.c type conversion futures)."""
        kinds = N.ELEM_KINDS
        fk = kinds.get(np.dtype(from_dtype).name)
        tk = kinds.get(np.dtype(to_dtype).name)
        if fk is None or tk is None:
            raise ValueError(
                f"cast datatype {name!r}: unsupported element type "
                f"(supported: {sorted(kinds)})")
        did = N.lib.ptc_register_datatype_cast(self._ptr, fk, tk, count)
        if did < 0:
            raise ValueError(f"bad cast datatype {name!r}")
        self.datatypes[name] = did
        self.datatype_bytes[name] = (
            None if count < 0 else count * np.dtype(to_dtype).itemsize)
        return did

    def reshape_stats(self):
        """(conversions, hits): local-reshape futures triggered vs
        memoized/identity reuses (avoidable-reshape accounting)."""
        conv = C.c_int64(0)
        hits = C.c_int64(0)
        N.lib.ptc_ctx_reshape_stats(self._ptr, C.byref(conv), C.byref(hits))
        return conv.value, hits.value

    # ------------------------------------------------------------ devices
    def device_queue_set_weight(self, qid: int, weight: float):
        """Relative device speed for best-device routing (reference:
        the per-device flop-rate weights, parsec/mca/device/device.h:137)."""
        N.lib.ptc_device_queue_set_weight(self._ptr, qid, float(weight))

    def device_queue_depth(self, qid: int) -> int:
        return N.lib.ptc_device_queue_depth(self._ptr, qid)

    def device_set_data_owner(self, handle: int, qid: int, version: int):
        """Stamp which device queue holds a current mirror of the copy
        with this handle (data-affinity routing; reference:
        parsec_get_best_device's owner pass, device.c:100-117)."""
        N.lib.ptc_device_set_data_owner(self._ptr, handle, qid, version)

    def device_clear_data_owner(self, handle: int, qid: int = -1):
        N.lib.ptc_device_clear_data_owner(self._ptr, handle, qid)

    def host_wrote(self, coll, m: int, n: int = 0):
        """A caller rewrote a collection tile's HOST bytes directly
        (numpy, outside the runtime): any device mirror of it is stale
        and must drop — the copy version cannot tell, no runtime write
        happened.  The serving engine's prompt/COW staging and the
        PagePool's copy-on-write clones route through here."""
        if not self._devices:
            return
        d = coll._datas.get((m, n))
        if d is None:
            return
        h = N.lib.ptc_copy_handle(N.lib.ptc_data_host_copy(d._ptr))
        if h:
            for dev in list(self._devices):
                dev._drop_mirror(h)
            N.lib.ptc_device_clear_data_owner(self._ptr, h, -1)

    def device_get_data_owner(self, handle: int):
        """(qid, version) of the stamped mirror owner, or (-1, 0)."""
        ver = C.c_int32(0)
        q = N.lib.ptc_device_get_data_owner(self._ptr, handle, C.byref(ver))
        return q, ver.value

    def device_set_affinity_skew(self, skew: float):
        """Spill guard for affinity routing: the owning queue loses to
        the least-loaded one when its load exceeds skew * best (<=0
        disables the affinity pass)."""
        N.lib.ptc_device_set_affinity_skew(self._ptr, float(skew))

    def device_queue_new(self) -> int:
        return N.lib.ptc_device_queue_new(self._ptr)

    def device_pop(self, qid: int, timeout_ms: int = 100):
        return N.lib.ptc_device_pop(self._ptr, qid, timeout_ms)

    def device_peek(self, qid: int, max_tasks: int = 64) -> list:
        """Observational snapshot of the ready tasks queued on a device
        queue (native ptc_peek_ready): [(task_ref, [(handle, size,
        version), ...]), ...].  Test/tooling probe — the peek pins are
        released before returning, so records must not be dereferenced;
        the prefetch lane consumes the span directly and holds its pins
        across the staging h2d."""
        words = max_tasks * (2 + 4 * N.MAX_FLOWS)
        buf = (C.c_int64 * words)()
        n = N.lib.ptc_peek_ready(self._ptr, qid, buf, words, max_tasks)
        out, w, pins = [], 0, []
        while w + 2 <= n:
            tref, nc = buf[w], buf[w + 1]
            w += 2
            recs = []
            for _ in range(nc):
                cptr, _dptr, size, ver = (buf[w], buf[w + 1], buf[w + 2],
                                          buf[w + 3])
                w += 4
                pins.append(cptr)
                recs.append((N.lib.ptc_copy_handle(cptr), size, ver))
            out.append((tref, recs))
        for cptr in pins:
            N.lib.ptc_copy_unpin(self._ptr, cptr)
        return out

    def device_peek_front(self, qid: int, max_tasks: int = 256) -> list:
        """Wave-granular ready-front census (native
        ptc_peek_ready_front): [(class_id, taskpool_ptr), ...] for the
        tasks still queued on `qid` — class ids only, nothing popped or
        pinned.  DTD tasks report class_id -1."""
        buf = (C.c_int64 * (2 * max_tasks))()
        n = N.lib.ptc_peek_ready_front(self._ptr, qid, buf, max_tasks)
        return [(buf[2 * i], buf[2 * i + 1]) for i in range(n)]

    def device_stats(self) -> dict:
        """Aggregated device-pipeline counters across this context's
        devices: prefetch hits/misses/staged bytes, reserve failures,
        spill traffic, dispatch-time h2d stall, and the counter-level
        overlap ratio — the fraction of h2d nanoseconds spent on the
        prefetch lane (overlapping compute) rather than stalling a
        dispatch; and the accumulate sweep's counts (`sweeps`,
        `sweep_hits` that added a task, `sweep_quiesced` closed by the
        engine's empty in-flight count, `sweep_ns`).  Per-device info
        objects ride along under "devices"."""
        devs = [dev.info() for dev in self._devices]
        keys = ("prefetch_staged", "prefetch_bytes", "prefetch_hits",
                "prefetch_misses", "prefetch_wasted", "reserve_fails",
                "spills", "spill_bytes", "h2d_stall_ns",
                "prefetch_h2d_ns", "ooc_waits", "h2d_hits", "h2d_bytes",
                "evictions", "stream_serves", "stream_slices",
                "stream_d2h_ns", "stream_bytes", "prefetch_wakeups",
                "cache_peak_bytes", "sweeps", "sweep_hits",
                "sweep_quiesced", "sweep_ns")
        agg = {k: sum(d["stats"].get(k, 0) for d in devs) for k in keys}
        moved = agg["prefetch_h2d_ns"] + agg["h2d_stall_ns"]
        agg["overlap_ratio"] = (
            round(agg["prefetch_h2d_ns"] / moved, 4) if moved else 0.0)
        agg["devices"] = devs
        return agg

    def task_complete(self, task_ptr):
        N.lib.ptc_task_complete(self._ptr, task_ptr)

    def task_fail(self, task_ptr):
        """Fail an ASYNC-owned task: aborts its taskpool (successors are
        never released; waiters observe the error)."""
        N.lib.ptc_task_fail(self._ptr, task_ptr)

    # ------------------------------------------------------------ profiling
    def profile_enable(self, enable=True):
        """Tracing level: 0/False off; 1 EXEC + comm spans only (the
        lean dispatch-bench setting — one buffer transaction per task);
        2/True adds RELEASE_DEPS spans and dep-EDGE pairs for DAG
        capture (parsec_tpu.profiling.to_dot).  PINS callbacks fire at
        any level (their key mask is the gate)."""
        level = 2 if enable is True else int(enable)
        N.lib.ptc_profile_enable(self._ptr, level)
        self._prof_level = level

    def profile_level(self) -> int:
        """Current trace level (0 off, 1 spans, 2 +edges)."""
        return N.lib.ptc_profile_level(self._ptr)

    def profile_ring(self, nbytes: Optional[int] = None) -> int:
        """Flight-recorder ring mode (runtime.trace_ring /
        PTC_MCA_runtime_trace_ring): bound each worker's trace buffer to
        `nbytes`, overwriting OLDEST whole events when full — long
        production runs keep the last-N-seconds tail instead of growing
        without bound, and a taskpool abort / lost peer auto-dumps it
        (see flight_dump).  Call with no argument to read the configured
        bytes-per-worker (0 = unbounded); reconfiguring clears buffered
        events, so arm it before the run."""
        if nbytes is not None:
            N.lib.ptc_profile_set_ring(self._ptr, int(nbytes))
        return N.lib.ptc_profile_ring(self._ptr)

    def profile_dropped(self) -> int:
        """Events overwritten before being taken (ring mode), summed
        across workers — the flight recorder's loss meter."""
        return N.lib.ptc_profile_dropped(self._ptr)

    def flight_dump(self, path: str) -> None:
        """Write the CURRENT trace buffers (without draining them) as a
        loadable .ptt v2 file — the flight-recorder sink.  The runtime
        fires this automatically (once) on taskpool abort and peer loss,
        to PTC_MCA_runtime_trace_dump or /tmp/ptc_flight.<rank>.ptt."""
        if N.lib.ptc_flight_dump(self._ptr, str(path).encode()) != 0:
            raise OSError(f"flight dump to {path!r} failed")

    def comm_clock(self) -> dict:
        """Clock-sync estimate against rank 0 (distributed tracing v2):
        offset_ns such that local_t + offset_ns ≈ rank 0's ptc_now_ns,
        measured from PING/PONG midpoints at comm bring-up and refreshed
        at each fence (minimum-RTT sample wins; err_ns is that RTT — the
        uncertainty bound).  Trace.merge applies it so merged timelines
        are causally consistent.  measured is False before the first
        sample (and in single-process contexts)."""
        buf = (C.c_int64 * 4)()
        N.lib.ptc_comm_clock_stats(self._ptr, buf)
        return {"offset_ns": buf[0], "err_ns": buf[1],
                "samples": buf[2], "measured": bool(buf[3])}

    def comm_clock_sync(self) -> int:
        """Force a fresh clock-sync probe burst (blocks up to ~2s for at
        least one sample); returns total samples accumulated."""
        return N.lib.ptc_comm_clock_sync(self._ptr)

    # ------------------------------------------------------------ metrics
    @property
    def metrics_enabled(self) -> bool:
        """Always-on latency histograms (runtime.metrics, default on):
        per-class EXEC duration, sampled release latency, h2d stall and
        comm/coll rendezvous wait, accumulated natively at the span-close
        paths — independent of the trace level."""
        return bool(N.lib.ptc_metrics_enabled(self._ptr))

    def metrics_enable(self, on: bool = True):
        N.lib.ptc_metrics_enable(self._ptr, 1 if on else 0)

    def metrics_histograms(self, merged: bool = False):
        """Decoded histogram records (profiling.metrics.Hist list);
        merged=True folds the fence-time peer snapshots (rank 0)."""
        from ..profiling.metrics import snapshot_histograms
        return snapshot_histograms(self, merged=merged)

    def metrics_registry(self):
        """The unified MetricsRegistry over this context (lazy,
        cached): histogram quantiles + Context.stats() counters, with
        Prometheus text export."""
        if getattr(self, "_metrics_registry", None) is None:
            from ..profiling.metrics import MetricsRegistry
            self._metrics_registry = MetricsRegistry(self)
        return self._metrics_registry

    def metrics_inflight(self) -> list:
        """Open EXEC bodies as (worker, class_name, begin_ns, scope_id)
        — the watchdog's stuck-task scan input (begin_ns is on the
        steady_clock/monotonic epoch; scope_id = the owning pool's
        request scope, 0 when unscoped)."""
        cap = 4 * (self.nb_workers + 2)
        buf = (C.c_int64 * cap)()
        n = N.lib.ptc_metrics_inflight(self._ptr, buf, cap)
        name_buf = C.create_string_buffer(256)
        out = []
        for i in range(0, n, 4):
            mid = buf[i + 1]
            k = N.lib.ptc_metrics_class_name(self._ptr, mid, name_buf, 256)
            name = name_buf.value.decode() if k > 0 else f"#{mid}"
            out.append((int(buf[i]), name, int(buf[i + 2]),
                        int(buf[i + 3])))
        return out

    def metrics_peer_rtts(self) -> list:
        """Fence-time clock-sync RTT per peer rank as seen by rank 0
        (zeros elsewhere / before the first fence) — the watchdog's
        slow-rank outlier input."""
        cap = max(1, self.nodes)
        buf = (C.c_int64 * cap)()
        n = N.lib.ptc_metrics_peer_rtts(self._ptr, buf, cap)
        return [int(buf[i]) for i in range(n)]

    def profile_take(self) -> np.ndarray:
        """Drain profiling buffers; returns an (n, 8) int64 array of
        (key, phase, class_id, local0, local1, worker, aux, t_ns).
        Loops with a fixed-size buffer until the native side reports
        empty.  See parsec_tpu.profiling for the dictionary + trace
        tooling built on top."""
        words = 8
        chunk_words = (1 << 16) * words
        buf = (C.c_int64 * chunk_words)()
        parts = []
        while True:
            n = N.lib.ptc_profile_take(self._ptr, buf, chunk_words)
            if n <= 0:
                break
            parts.append(np.ctypeslib.as_array(buf, shape=(chunk_words,))[:n]
                         .copy())
            if n < chunk_words:
                break
        if not parts:
            return np.empty((0, words), dtype=np.int64)
        return np.concatenate(parts).reshape(-1, words)
