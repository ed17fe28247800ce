#!/usr/bin/env python
"""Chip smoke test: the dense-Cholesky path of the runtime on a TPU.

One process, no child processes.  Drives the path a user calls —
Context -> TwoDimBlockCyclic -> TpuDevice -> build_potrf_panels /
build_potrf -> run/wait — on a matrix generated on the device from a
seed, and checks the result against the generator.

  python chip_smoke.py             one chip: phase `panel` (fp32
                                   N=32768 NB=512) and phase `tile`
                                   (fp32 N=8192 NB=512)
  python chip_smoke.py --chips 4   four chips, only phase `panels4`:
                                   fp32 N=65536 NB=512, panel-cyclic
                                   over four colocated ranks in this
                                   one process, one chip each

Each phase runs twice (the first run compiles; the second is checked)
and fails the script unless
  (a) the device executed every task of the DAG (a count from NT), so
      no task ran as a CPU chore;
  (b) no wave fell back to per-task dispatch;
  (c) the runtime printed no `ptc:` warning;
  (d) ||L(L^T x) - Ax|| / (||A||_1 ||x||) for seeded random x, on the
      device at HIGHEST, and the float64 host check of a few blocks of
      L L^T (componentwise) are both within sqrt(N) * eps32.
The four-chip phase also checks that every chip did its share of the
map and that cross-chip panels moved device-to-device.

Exits non-zero, and prints no result line, when JAX finds no TPU.  The
last line of a passing run is
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
import argparse
import json
import os
import socket
import sys
import threading
import time

import numpy as np

SEED = 0


class _StderrWatch:
    """Tee fd 2 (Python and native stderr alike) through a pipe, keeping
    every line the runtime prefixes with `ptc:`."""

    def __enter__(self):
        sys.stderr.flush()
        self.lines = []
        self._saved = os.dup(2)
        r, w = os.pipe()
        os.dup2(w, 2)
        os.close(w)
        self._pump = threading.Thread(target=self._read, args=(r,),
                                      daemon=True)
        self._pump.start()
        return self

    def _read(self, r):
        with os.fdopen(r, "rb") as f:
            for line in f:
                os.write(self._saved, line)
                if line.startswith(b"ptc:"):
                    self.lines.append(line.decode(errors="replace").strip())

    def __exit__(self, *exc):
        sys.stderr.flush()
        os.dup2(self._saved, 2)  # drops the pipe's last writer: EOF
        self._pump.join(timeout=10)
        os.close(self._saved)
        return False


def _potrf_tasks(variant, nt):
    if variant == "panel":
        return nt + nt * (nt - 1) // 2              # F(k) + U(k, j)
    return (nt + nt * (nt - 1)                      # POTRF, TRSM + SYRK
            + nt * (nt - 1) * (nt - 2) // 6)        # GEMM


def _panel_tasks_of_rank(nt, nranks, r):
    """F(k) runs on the owner of panel k, U(k, j) on the owner of j."""
    owned = [j for j in range(nt) if j % nranks == r]
    return len(owned) + sum(j for j in owned)


def _device_report(dev):
    s = dev.stats
    keys = ("tasks", "batches", "batched_tasks", "spec_hits", "h2d_bytes",
            "d2h_bytes", "d2d_bytes", "dp_d2d_bytes", "dp_recv_bytes",
            "spills", "batch_fallbacks", "cache_peak_bytes")
    return {k: s[k] for k in keys}


def _phase_failures(st, warnings, want_tasks):
    bad = []
    if st["tasks"] != want_tasks:
        bad.append(f"(a) device ran {st['tasks']} of {want_tasks} tasks")
    if st["batch_fallbacks"]:
        bad.append(f"(b) batch_fallbacks={st['batch_fallbacks']}")
    if warnings:
        bad.append(f"(c) runtime warnings: {warnings[:5]}")
    return bad


def _check(parts, n, label):
    """(d): both residuals against sqrt(N) * eps32; returns failures."""
    from parsec_tpu.device.bench_utils import (potrf_host_check,
                                               potrf_residual,
                                               residual_bound)
    t0 = time.perf_counter()
    resid = potrf_residual(parts, SEED)
    host = potrf_host_check(parts, SEED)
    bound = residual_bound(n)
    print(f"{label}: residual ||L(L^T x)-Ax||/(||A||_1||x||) = {resid!r}; "
          f"host float64 componentwise |LL^T-A|/(|L||L^T|) = {host!r}; "
          f"bound sqrt(N)*eps32 = {bound!r} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)
    bad = []
    if not resid <= bound:
        bad.append(f"(d) residual {resid!r} > {bound!r}")
    if not host <= bound:
        bad.append(f"(d) host check {host!r} > {bound!r}")
    return bad


def run_phase(name, variant, n, nb):
    """One rank, one chip: two runs, the second checked."""
    import parsec_tpu as pt
    from parsec_tpu.algos import build_potrf, build_potrf_panels
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device.bench_utils import (
        generate_spd_on_device, generate_spd_panels_on_device,
        spotrf_device, wait_device_tiles)
    nt = n // nb
    want = _potrf_tasks(variant, nt)
    bad = []
    for rep, what in enumerate(("compile+run", "run")):
        with _StderrWatch() as watch, pt.Context(nb_workers=4) as ctx:
            A = TwoDimBlockCyclic(n, n, n if variant == "panel" else nb,
                                  nb, dtype=np.float32)
            A.register(ctx, "A")
            dev = spotrf_device(ctx, A)
            gen = (generate_spd_panels_on_device if variant == "panel"
                   else generate_spd_on_device)
            t0 = time.perf_counter()
            gen(dev, A, seed=SEED).block_until_ready()
            t_gen = time.perf_counter() - t0
            build = build_potrf_panels if variant == "panel" else build_potrf
            tp = build(ctx, A, dev=dev)
            t0 = time.perf_counter()
            tp.run()
            tp.wait()
            wait_device_tiles(dev, A)
            wall = time.perf_counter() - t0
            st = _device_report(dev)
            print(f"{name} {what}: N={n} NB={nb} tasks expected {want}, "
                  f"generate {t_gen:.2f} s, factor wall {wall:.2f} s",
                  flush=True)
            print(f"{name} {what}: dev.stats {json.dumps(st)}", flush=True)
            if rep == 1:
                bad += _check([(dev, A)], n, name)
            dev.stop()
        bad += [f"{what}: {b}" for b in
                _phase_failures(st, watch.lines, want)]
    peak = (dev.device.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"{name}: device peak_bytes_in_use {peak}", flush=True)
    return bad


def _free_base_port(nranks):
    for base in range(29400, 60000, 97):
        try:
            for r in range(nranks):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", base + r))
            return base
        except OSError:
            continue
    raise RuntimeError("no free loopback ports")


def run_colocated_phase(name, n, nb, nranks):
    """`nranks` runtime ranks in THIS process, rank r on chip r, panels
    cyclic over ranks.  Colocated ranks hand device payloads over by
    reference: a factored panel reaches the other chips device-to-device."""
    import jax

    import parsec_tpu as pt
    from parsec_tpu.algos import build_potrf_panels
    from parsec_tpu.data import TwoDimBlockCyclic
    from parsec_tpu.device.bench_utils import (
        generate_spd_panels_on_device, spotrf_device, wait_device_tiles)
    nt = n // nb
    devices = jax.devices()[:nranks]
    bad = []
    for rep, what in enumerate(("compile+run", "run")):
        base = _free_base_port(nranks)
        ran = threading.Barrier(nranks + 1)
        checked = threading.Barrier(nranks + 1)
        parts, errors, walls = [None] * nranks, [], [0.0] * nranks

        def rank(r):
            try:
                ctx = pt.Context(nb_workers=2)
                ctx.set_rank(r, nranks)
                ctx.comm_init(base)
                ctx.comm_set_colocated([q for q in range(nranks) if q != r])
                with ctx:
                    A = TwoDimBlockCyclic(n, n, n, nb, P=1, Q=nranks,
                                          nodes=nranks, myrank=r,
                                          dtype=np.float32)
                    A.register(ctx, "A")
                    dev = spotrf_device(ctx, A, jax_device=devices[r])
                    generate_spd_panels_on_device(
                        dev, A, seed=SEED).block_until_ready()
                    tp = build_potrf_panels(ctx, A, dev=dev)
                    ctx.comm_fence()
                    t0 = time.perf_counter()
                    tp.run()
                    tp.wait()
                    wait_device_tiles(dev, A)
                    ctx.comm_fence()
                    walls[r] = time.perf_counter() - t0
                    parts[r] = (dev, A)
                    ran.wait()
                    checked.wait()   # the main thread reads every part
                    dev.stop()
                    ctx.comm_fini()
            except Exception:
                import traceback
                errors.append(f"rank {r}: {traceback.format_exc()}")
                ran.abort()
                checked.abort()

        with _StderrWatch() as watch:
            threads = [threading.Thread(target=rank, args=(r,))
                       for r in range(nranks)]
            for t in threads:
                t.start()
            try:
                ran.wait()
                print(f"{name} {what}: N={n} NB={nb} on {nranks} chips, "
                      f"factor wall {max(walls):.2f} s", flush=True)
                reports = [_device_report(p[0]) for p in parts]
                for r, st in enumerate(reports):
                    print(f"{name} {what}: rank {r} on {devices[r]} "
                          f"dev.stats {json.dumps(st)}", flush=True)
                if rep == 1:
                    bad += _check(parts, n, name)
                checked.wait()
            except threading.BrokenBarrierError:
                pass
            for t in threads:
                t.join()
        if errors:
            return bad + errors
        for r, st in enumerate(reports):
            want = _panel_tasks_of_rank(nt, nranks, r)
            bad += [f"{what}: rank {r}: {b}" for b in _phase_failures(
                st, watch.lines if r == 0 else [], want)]
        d2d = sum(st["dp_d2d_bytes"] for st in reports)
        host = sum(st["dp_recv_bytes"] + st["h2d_bytes"] + st["d2h_bytes"]
                   for st in reports)
        print(f"{name} {what}: cross-chip dp_d2d_bytes {d2d}, host payload "
              f"bytes (dp_recv + h2d + d2h) {host}", flush=True)
        if d2d <= 0 or host:
            bad.append(f"{what}: panels did not move device-to-device "
                       f"(dp_d2d_bytes={d2d}, host bytes={host})")
    return bad


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from parsec_tpu.utils.compile_cache import place_compile_cache
    except ImportError as e:
        print(f"chip_smoke: not in a checkout of the repo ({e})",
              file=sys.stderr)
        return 2
    cache = place_compile_cache()
    import importlib.metadata

    import jax
    import jaxlib
    devs = jax.devices()
    d0 = devs[0]
    print(f"devices: platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {d0.platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devs)}",
              file=sys.stderr)
        return 1
    from parsec_tpu import _native
    from parsec_tpu.algos import potrf
    from parsec_tpu.device.bench_utils import hbm_bytes
    print(f"bytes_limit: {hbm_bytes(d0)}", flush=True)
    print(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {importlib.metadata.version('libtpu')}", flush=True)
    print(f"native core source hash: {_native.SOURCE_HASH}", flush=True)
    print(f"compile cache: {cache}", flush=True)
    print(f"kernel matmul precision: {potrf.MATMUL_PRECISION} "
          f"(algos/potrf.py MATMUL_PRECISION); jax_default_matmul_precision="
          f"{jax.config.jax_default_matmul_precision}", flush=True)
    if args.chips == 4:
        phases = [("panels4", lambda: run_colocated_phase(
            "panels4", 65536, 512, 4))]
    else:
        phases = [("panel", lambda: run_phase("panel", "panel", 32768, 512)),
                  ("tile", lambda: run_phase("tile", "tile", 8192, 512))]
    failed = {}
    for name, run in phases:
        t0 = time.perf_counter()
        bad = run()
        print(f"phase {name}: {'FAIL' if bad else 'ok'} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
        if bad:
            failed[name] = bad
            for b in bad:
                print(f"  {name}: {b}", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {sorted(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
