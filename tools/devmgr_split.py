#!/usr/bin/env python
"""Where a chip's idle time goes, by what its device manager was doing.

The device manager traces its loop into the profiler's trace as three
spans on its thread (parsec_tpu.profiling.trace.ManagerSpans):
`ptc.devmgr.ready_wait`, `ptc.devmgr.batch_wait` and
`ptc.devmgr.dispatch`, and JAX traces its own program builds there
(BUILD_SPANS).  For each chip this splits the time inside the benchmark's
`solve` spans in which the chip ran no operation into four disjoint
shares of the solve time, in this precedence:

  devmgr.build_idle        the manager was building a program
  devmgr.batch_wait_idle   it was sweeping for more tasks of a batch
  devmgr.dispatch_idle     it was dispatching, not building
  runtime.ready_wait_idle  it waited for the engine to release a task

and the rest, with no manager span; all five sum to the benchmark's
`device.idle_share`.  Means over chips, in %.

  python tools/devmgr_split.py --workload <cell> --seed <n> --seconds <s>
      one traced run of a BENCHMARK.json cell (benchmark/run.py with
      --trace 1, its result line as usual), then the split of its trace
  python tools/devmgr_split.py --dump-in <file.json.gz>
      the split of a trace reduced by --dump

The last line of standard output is one JSON object: the split, the
clock offset from the native trace clock to the profiler's
(`profiler_offset_ns` over the dispatch spans: median, spread, and the
drift between the first and last tenth of the spans), the span counts,
how the batch_wait sweeps closed (`sweep_close`: % by the engine's
empty in-flight count, by the deadline, by batch_max), the flows the
window gathered outside their wave program (`eager_gathers`, the
dispatch spans' `eager` args), with --workload the device's
`gather_shapes` and, in a panel cell, the panel update's rows the
device multiplied per row the factorization needs (`update_rows`), and
the host spans on the managers' threads that cover the most idle time.
`--dump <file.json.gz>` also writes the reduced trace: busy intervals,
solves and the managers' threads' spans.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import flops  # noqa: E402
from benchmark import trace_reduce as T  # noqa: E402
from parsec_tpu.profiling.trace import (profiler_offset_ns,  # noqa: E402
                                        xplane_spans)

# JAX's own spans around a program build, as a TPU trace names them:
# tracing to a jaxpr, lowering, and compiling or loading from the cache
BUILD_SPANS = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
               "backend_compile_and_load")
# how a `ptc.devmgr.batch_wait` sweep closed, its `closed` arg: the
# engine had nothing left in flight, the batch_wait_ms deadline passed,
# or the batch reached batch_max
CLOSES = ("quiesced", "deadline", "full")
# the panel update's ops in a v5e trace: the fusion that holds the
# update's dot and its subtract, f32[lanes, rows, NB] or f32[rows, NB]
# (all N rows of a panel, or one row block of them), named by its HLO
# text as trace_reduce reads it
UPDATE_OP = r"^%?\w*subtract\w*_fusion(\.\d+)? = f32\[(?:(\d+),)?(\d+),{NB}\]"
STATES = (("devmgr.build_idle", None),
          ("devmgr.batch_wait_idle", "ptc.devmgr.batch_wait"),
          ("devmgr.dispatch_idle", "ptc.devmgr.dispatch"),
          ("runtime.ready_wait_idle", "ptc.devmgr.ready_wait"))


def manager_lines(spans):
    """chip -> the host-plane lines that carry its manager's spans."""
    out = {}
    for name, _, _, line, args in spans:
        if name.startswith("ptc.devmgr.") and "chip" in args:
            out.setdefault(int(args["chip"]), set()).add(line)
    return out


def split(tr: T.Trace, spans, build=BUILD_SPANS) -> dict:
    """The four shares and the rest (% of solve time, mean over chips)
    of a trace_reduce.Trace, given the host spans
    (name, start, end, line, args) of xplane_spans; also
    `device.idle_share`, which they sum to."""
    lines = manager_lines(spans)
    tot = tr.solve_s() * 1e9
    keys = [k for k, _ in STATES] + ["rest"]
    out = dict.fromkeys(keys + ["device.idle_share"], 0.0)
    if not tot or not tr.busy:
        return out
    for chip, busy in tr.busy.items():
        mine = lines.get(chip, set())
        layers, acc = [], []
        for _, name in STATES:
            acc += [(s, e) for n, s, e, line, args in spans
                    if (n == name and args.get("chip") == chip) or
                    (name is None and n in build and line in mine)]
            layers.append(T.union(acc))
        ns = dict.fromkeys(keys, 0)
        for lo, hi in tr.solves:
            for g in T.gaps(busy, lo, hi):
                prev = 0
                for (k, _), merged in zip(STATES, layers):
                    c = T.covered(merged, *g)
                    ns[k] += c - prev
                    prev = c
                ns["rest"] += g[1] - g[0] - prev
        for k in keys:
            out[k] += 100.0 * ns[k] / tot / len(tr.busy)
        out["device.idle_share"] += 100.0 * sum(ns.values()) / tot \
            / len(tr.busy)
    return out


def clock(spans) -> dict:
    """The native-to-profiler clock offset from the dispatch spans."""
    pairs = sorted((a["ptc_ns"], s) for n, s, _, _, a in spans
                   if n == "ptc.devmgr.dispatch" and "ptc_ns" in a)
    if not pairs:
        return {}
    off, spread = profiler_offset_ns(pairs)
    tenth = max(1, len(pairs) // 10)
    return {"offset_ns": off, "spread_ns": spread, "pairs": len(pairs),
            "drift_ns": profiler_offset_ns(pairs[-tenth:])[0]
            - profiler_offset_ns(pairs[:tenth])[0],
            "over_s": (pairs[-1][1] - pairs[0][1]) / 1e9}


def sweep_close(spans) -> dict:
    """The share (%) of the sweeps that carry a `closed` arg closed each
    way (CLOSES), and their count `sweeps`; {} when none carries it."""
    closed = [a["closed"] for n, _, _, _, a in spans
              if n == "ptc.devmgr.batch_wait" and "closed" in a]
    if not closed:
        return {}
    out = {k: 100.0 * closed.count(k) / len(closed) for k in CLOSES}
    out["sweeps"] = len(closed)
    return out


def update_rows(tr: T.Trace, cfg: dict):
    """The panel update's rows the device multiplied inside the solves,
    lanes times rows of each update op run, per useful row: the rows at
    and below block row j of each U(k, j) (benchmark/flops.py's count
    over 2 NB^2).  None outside a panel cell or where no op matches."""
    if cfg.get("dag") != "panels" or not tr.solves:
        return None
    nb = cfg["NB"]
    pat = re.compile(UPDATE_OP.replace("{NB}", str(nb)))
    starts = [s for s, _ in tr.solves]
    rows = 0
    for ops in tr.ops.values():
        for text, s, _ in ops:
            m = pat.search(text)
            if not m or "kind=kOutput" not in text:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < tr.solves[i][1]:
                rows += int(m.group(2) or 1) * int(m.group(3))
    useful = flops.panel_update(cfg["N"], nb) / (2 * nb * nb)
    return rows / useful / len(tr.solves) if rows else None


def top_idle_spans(tr: T.Trace, spans, n: int = 25) -> list:
    """The names of the spans on the managers' threads whose union
    covers the most idle time inside the solves: [name, seconds]."""
    lines = set().union(*manager_lines(spans).values() or [set()])
    by = {}
    for name, s, e, line, _ in spans:
        if line in lines:
            by.setdefault(name, []).append((s, e))
    cov = {}
    for name, iv in by.items():
        merged = T.union(iv)
        cov[name] = sum(T.covered(merged, *g) for b in tr.busy.values()
                        for lo, hi in tr.solves
                        for g in T.gaps(b, lo, hi)) / 1e9
    return sorted(([k, v] for k, v in cov.items() if v > 0),
                  key=lambda kv: -kv[1])[:n]


def report(tr: T.Trace, spans, stats=None, cfg=None) -> dict:
    """The split and the rest of the result line; `stats`, the device's
    dev.stats at the end of the run, gives `gather_shapes`, and `cfg`,
    the cell's configuration, `update_rows`."""
    counts = {}
    for name, *_ in spans:
        if name.startswith("ptc.devmgr."):
            counts[name] = counts.get(name, 0) + 1
    out = {"split": split(tr, spans), "clock": clock(spans),
           "spans": counts, "sweep_close": sweep_close(spans),
           "eager_gathers": sum(a.get("eager", 0) for n, _, _, _, a in spans
                                if n == "ptc.devmgr.dispatch"),
           "top_idle_spans": top_idle_spans(tr, spans)}
    if stats is not None:
        out["gather_shapes"] = stats.get("gather_shapes")
    if cfg is not None:
        out["update_rows"] = update_rows(tr, cfg)
    return out


def dump(path: str, tr: T.Trace, spans) -> None:
    """The reduced trace: merged busy intervals per chip, the benchmark's
    host spans, and every span on the managers' threads."""
    lines = set().union(*manager_lines(spans).values() or [set()])
    with gzip.open(path, "wt") as f:
        json.dump({"busy": {str(c): b for c, b in tr.busy.items()},
                   "host": tr.host,
                   "spans": [s for s in spans if s[3] in lines]}, f)


def undump(path: str):
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    ops = {int(c): [("busy", s, e) for s, e in b]
           for c, b in d["busy"].items()}
    tr = T.Trace(ops, [tuple(h) for h in d["host"]], len(ops))
    return tr, [tuple(s) for s in d["spans"]]


def traced_run(argv, stats: dict = None, cfg: dict = None,
               **main_kw) -> tuple:
    """One `benchmark/run.py --trace 1` run in this process (`main_kw`
    go to its main()); its trace (trace_reduce.Trace) and host spans,
    read before run.py removes the file.  `stats`, a dict, takes the
    device's dev.stats as the run stops it, and `cfg`, a dict, the
    cell's configuration."""
    from benchmark import harness, run
    kept = {}
    load = T.Trace.load.__func__
    finish = harness.Rank.finish

    def keep_stats(rank):
        if stats is not None:
            stats.update(rank.dev.stats)
        if cfg is not None:
            cfg.update(rank.cfg)
        finish(rank)

    def keep(cls, trace_dir, nchips):
        tr = load(cls, trace_dir, nchips)
        f, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
        kept["spans"] = xplane_spans(f, "ptc.devmgr.", threads=True)
        kept["trace"] = tr
        return tr

    T.Trace.load = classmethod(keep)
    harness.Rank.finish = keep_stats
    try:
        rc = run.main(argv + ["--trace", "1"], **main_kw)
    finally:
        T.Trace.load = classmethod(load)
        harness.Rank.finish = finish
    if rc or "trace" not in kept:
        raise SystemExit(rc or 1)
    return kept["trace"], kept["spans"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--workload")
    src.add_argument("--dump-in")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--dump", help="write the reduced trace here")
    args = ap.parse_args(argv)
    stats = cfg = None
    if args.workload:
        stats, cfg = {}, {}
        tr, spans = traced_run(["--workload", args.workload, "--seed",
                                str(args.seed), "--seconds",
                                str(args.seconds)], stats, cfg)
    else:
        tr, spans = undump(args.dump_in)
    if args.dump:
        dump(args.dump, tr, spans)
    print(json.dumps(report(tr, spans, stats, cfg)), flush=True)
    return 0


if __name__ == "__main__":
    # the checkout's compile cache, as benchmark/run.py places it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.exit(main())
