#!/usr/bin/env python
"""Transfer-economics harness (reference roles: tests/apps/pingpong/
bandwidth.jdf for the transport, tools/gpu/testbandwidth for the device
staging path) — validates dispatch/transfer economics on loopback.

Two SPMD processes over loopback TCP run rank-hopping RW chains whose
datum is a tile of the given size; every hop is one full cross-rank
payload transfer.  ONE persistent process pair serves an entire path
sweep — all sizes and reps share the TCP mesh, the device, the jit
cache and (for PK_DEVICE) the transfer sessions — so the numbers
measure steady-state per-transfer cost, with the first (warmup) rep's
wall reported separately as `setup_ms` (session establishment, first
compile, first staging).  That split is the point: the old
per-process-pair, per-rep-recompile measurement charged ~100 ms of
setup to every transfer (BASELINE.md row 1d, 118 ms / 4 MiB).

Paths swept (each in its own process pair, selected by env knobs):
  eager   — payloads ride inline in ACTIVATE frames (eager_limit huge)
  rdv     — every payload pulled via GET rendezvous (eager_limit 0);
            payloads above comm.chunk_size stream as pipelined chunks
  device  — TpuDevice attached (jax CPU backend: each rank is its own
            process, and only one process may hold a chip): payloads
            ride the PK_DEVICE device data plane (d2h at serve / h2d at
            deliver)

Per path the harness fits  t(size) = fixed_overhead + size * per_byte
by least squares over the per-size minima and reports both legs — the
same two quantities the adaptive eager threshold is derived from, so
the model is checkable against the engine's own calibration (also
reported, from a dedicated eager_limit=auto run).

ptc-topo: `--classed` (or an explicit `--classes ici,dcn`) re-runs the
wire paths per LINK CLASS and publishes the per-class fits under
doc["classes"] = {cls: {path: {"fit": ...}}} — exactly the shape
TransferEconomics.load consumes for class-aware pricing.  On loopback
the dcn class is EMULATED with the native per-peer fault delay map
(PTC_COMM_FAULT_DELAY_MAP, --dcn-delay-us µs per recv) — the same
deterministic island emulator the topology tests use; on a real
multi-host deployment run the harness once per link class between
hosts of that class and merge the docs.

  python tools/testbandwidth.py                        # full sweep
  python tools/testbandwidth.py --paths device --sizes 4194304
  python tools/testbandwidth.py --quick --json /tmp/comm.json
  python tools/testbandwidth.py --quick --classed      # + per-class fits
  make bench-comm                                      # BENCH-style file
"""
import json
import multiprocessing as mp
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

_PATH_ENV = {
    # eager: everything inline (rendezvous never engages)
    "eager": {"PTC_MCA_comm_eager_limit": str(1 << 30)},
    # rdv: everything pulled (chunked above comm.chunk_size)
    "rdv": {"PTC_MCA_comm_eager_limit": "0"},
    # device: rendezvous forced so device-resident payloads advertise
    # PK_DEVICE transfer tags
    "device": {"PTC_MCA_comm_eager_limit": "0"},
}


def _bump(x):
    # module-level ON PURPOSE: the process-wide jit cache keys on kernel
    # identity, so every taskpool of a sweep reuses ONE compiled
    # executable per shape.  A per-rep lambda would recompile each rep —
    # exactly the setup cost the old 118 ms/4 MiB number was paying.
    return x + 1.0


def _worker(rank, port, sizes, hops, reps, path, env, q):
    try:
        for k, v in env.items():
            os.environ[k] = v
        os.environ["JAX_PLATFORMS"] = "cpu"  # rank processes stay off the chip
        import parsec_tpu as pt

        ctx = pt.Context(nb_workers=1)
        ctx.set_rank(rank, 2)
        ctx.comm_init(port)
        dev = None
        if path == "device":
            from parsec_tpu.device import TpuDevice
            dev = TpuDevice(ctx)
        k = pt.L("k")
        out = []
        for si, size in enumerate(sizes):
            elems = max(1, size // 4)
            arr = np.zeros((2, elems), dtype=np.float32)
            ctx.register_linear_collection(f"A{si}", arr, elem_size=size,
                                           nodes=2, myrank=rank)
            ctx.register_arena(f"t{si}", size)

            def build():
                tp = pt.Taskpool(ctx, globals={"NB": hops})
                tc = tp.task_class("Hop")
                tc.param("k", 0, pt.G("NB"))
                tc.affinity(f"A{si}", k % 2)
                tc.flow("A", "RW",
                        pt.In(pt.Mem(f"A{si}", 0), guard=(k == 0)),
                        pt.In(pt.Ref("Hop", k - 1, flow="A")),
                        pt.Out(pt.Ref("Hop", k + 1, flow="A"),
                               guard=(k < pt.G("NB"))),
                        arena=f"t{si}")
                if dev is not None:
                    dev.attach(tc, tp, kernel=_bump, reads=["A"],
                               writes=["A"], shapes={"A": (elems,)},
                               dtype=np.float32)
                else:
                    tc.body_noop()
                return tp

            walls = []
            for rep in range(reps + 1):  # rep 0 = setup (reported apart)
                tp = build()
                ctx.comm_fence()  # both ranks ready: isolate the chain
                t0 = time.perf_counter()
                tp.run()
                tp.wait()
                ctx.comm_fence()
                walls.append(time.perf_counter() - t0)
            out.append({"size_bytes": size, "setup_ms": walls[0] * 1e3,
                        "walls": walls[1:]})
        tuning = ctx.comm_tuning()
        dstats = dict(dev.stats) if dev is not None else None
        if dev is not None:
            dev.stop()
        ctx.comm_fini()
        ctx.destroy()
        q.put(("ok", rank, out, tuning, dstats))
    except Exception:
        import traceback
        q.put(("err", rank, traceback.format_exc(), None, None))


# the fit lives in parsec_tpu/comm/economics.py now: the topology
# selector consumes exactly the model this harness publishes, so the
# two can never diverge (and ROADMAP item 5's per-link-class routing
# reuses the same loader)
from parsec_tpu.comm.economics import fit_points as _fit  # noqa: E402


def run_path(path, sizes, hops, reps, port, extra_env=None):
    """Sweep all `sizes` on one persistent 2-process pair; returns the
    path's report dict (latencies, setup costs, fit, tunables)."""
    env = dict(_PATH_ENV[path])
    env.update(extra_env or {})
    mpctx = mp.get_context("spawn")
    q = mpctx.Queue()
    procs = [mpctx.Process(target=_worker,
                           args=(r, port, sizes, hops, reps, path, env, q))
             for r in range(2)]
    for p in procs:
        p.start()
    try:
        res = [q.get(timeout=1800) for _ in range(2)]
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    errs = [r for r in res if r[0] != "ok"]
    if errs:
        raise RuntimeError(str(errs))
    # per size: the transfer completes on the slower side
    by_rank = {r[1]: r for r in res}
    rows, points = [], []
    for si, size in enumerate(sizes):
        walls = [max(by_rank[0][2][si]["walls"][i],
                     by_rank[1][2][si]["walls"][i])
                 for i in range(len(by_rank[0][2][si]["walls"]))]
        per_transfer = [w / hops for w in walls]
        best = min(per_transfer)
        rows.append({
            "size_bytes": size,
            "setup_ms": round(max(by_rank[0][2][si]["setup_ms"],
                                  by_rank[1][2][si]["setup_ms"]), 2),
            "per_transfer_ms": round(best * 1e3, 3),
            "per_transfer_ms_all": [round(t * 1e3, 3)
                                    for t in per_transfer],
            "gbps": round(size * 8 / best / 1e9, 3),
        })
        points.append((size, best))
    return {
        "sizes": rows,
        "fit": _fit(points),
        "tunables": by_rank[0][3],
        "device_stats": by_rank[0][4],
    }


def run_adaptive_probe(port):
    """One tiny eager_limit=auto job, reported so every sweep records
    what threshold the engine would derive on this host (the measured
    RTT and memcpy legs come back via comm_tuning)."""
    rep = run_path("eager", [4096], hops=8, reps=1, port=port,
                   extra_env={"PTC_MCA_comm_eager_limit": "auto"})
    t = rep["tunables"]
    return {"derived_eager_limit": t["eager_limit"],
            "rtt_ns": t["rtt_ns"], "memcpy_bps": t["memcpy_bps"]}


def _arg(flag, default=None):
    if flag in sys.argv:
        return sys.argv[sys.argv.index(flag) + 1]
    return default


def main():
    quick = "--quick" in sys.argv
    sizes = [65536, 1048576, 4194304] if not quick else [4096, 65536]
    hops = int(_arg("--hops", 8 if quick else 16))
    reps = int(_arg("--reps", 2 if quick else 3))
    paths = ["eager", "rdv", "device"]
    if "--device" in sys.argv:  # legacy spelling
        paths = ["device"]
    if _arg("--paths"):
        paths = _arg("--paths").split(",")
    if _arg("--sizes"):
        sizes = [int(x) for x in _arg("--sizes").split(",")]
    base = int(os.environ.get("PTC_PORT", "31300"))
    # shared provenance/oversubscription capture (bench.host_provenance
    # replaced this harness's private copy): 2 ranks x (worker + comm
    # thread [+ device lanes on the device path])
    from bench import host_provenance
    doc = {
        "bench": "transfer_economics",
        "when": time.strftime("%Y-%m-%d %H:%M:%S"),
        **host_provenance(threads=2 * 2),
        "meta": {"hops": hops, "reps": reps, "sizes": sizes,
                 "nodes": 2,
                 "platform": "cpu-loopback"},
        "paths": {},
    }
    port = base
    try:
        doc["adaptive_eager"] = run_adaptive_probe(port)
    except Exception as e:
        doc["adaptive_eager"] = {"error": str(e)[:300]}
    port += 4
    for path in paths:
        try:
            doc["paths"][path] = run_path(path, sizes, hops, reps, port)
        except Exception as e:
            doc["paths"][path] = {"error": str(e)[:300]}
        print(json.dumps({path: doc["paths"][path]}), flush=True)
        port += 4
    # ptc-topo classed sweep: the wire paths again, once per link
    # class.  ici = the plain loopback wire; dcn = the same wire under
    # the per-peer fault delay map (deterministic island emulation).
    # The device path is skipped — staging is class-independent.
    if "--classed" in sys.argv or _arg("--classes"):
        cls_list = [c for c in (_arg("--classes") or "ici,dcn").split(",")
                    if c]
        dcn_us = int(_arg("--dcn-delay-us", "150"))
        doc["meta"]["dcn_delay_us"] = dcn_us
        doc["classes"] = {}
        for cls_name in cls_list:
            extra = {}
            if cls_name == "dcn":
                extra = {"PTC_COMM_FAULT_DELAY_MAP":
                         f"0:{dcn_us},1:{dcn_us}"}
            doc["classes"][cls_name] = {}
            for path in paths:
                if path == "device":
                    continue
                try:
                    doc["classes"][cls_name][path] = run_path(
                        path, sizes, hops, reps, port, extra_env=extra)
                except Exception as e:
                    doc["classes"][cls_name][path] = \
                        {"error": str(e)[:300]}
                print(json.dumps(
                    {f"{cls_name}.{path}":
                     doc["classes"][cls_name][path]}), flush=True)
                port += 4
    out = _arg("--json")
    if out:
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {out}", file=sys.stderr)
    else:
        print(json.dumps(doc), flush=True)


if __name__ == "__main__":
    main()
