"""tools/devmgr_split.py: each chip's idle time inside the solves split
by the device manager's state, on planted traces with exact shares, on
the recorded v5e trace, and through one traced benchmark run on the
CPU."""
import contextlib
import importlib.util
import io
import json
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import trace_reduce as T  # noqa: E402


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


S = _load("devmgr_split", "tools", "devmgr_split.py")
IDLE = _load("device_idle_share", "benchmark", "metrics",
             "device.idle_share.py")
FIXTURE = os.path.join(REPO, "tests", "bench", "fixtures",
                       "tile_n2048_v5e.xplane.pb")
SHARES = [k for k, _ in S.STATES] + ["rest"]


def span(name, s, e, line=0, chip=0, **args):
    if name.startswith("ptc.devmgr."):
        args["chip"] = chip
    return (name, s, e, line, args)


def trace(busy):
    """One window and one solve over [0, 1000); chip c ran ops over
    busy[c]."""
    ops = {c: [("%op = f32[1]", s, e) for s, e in iv]
           for c, iv in enumerate(busy)}
    return T.Trace(ops, [("window", 0, 1000), ("solve", 0, 1000)],
                   len(busy))


def check_sum(tr, out):
    """The shares and the rest add up to device.idle_share."""
    idle = IDLE.read(SimpleNamespace(trace=tr))
    assert sum(out[k] for k in SHARES) == pytest.approx(idle)
    assert out["device.idle_share"] == pytest.approx(idle)


@pytest.mark.parametrize("state,spans", [
    ("runtime.ready_wait_idle", [span("ptc.devmgr.ready_wait", 200, 500)]),
    ("devmgr.batch_wait_idle", [span("ptc.devmgr.batch_wait", 200, 500)]),
    ("devmgr.dispatch_idle", [span("ptc.devmgr.dispatch", 200, 500)]),
    # a build on the manager's thread, known by its other spans
    ("devmgr.build_idle", [span("ptc.devmgr.ready_wait", 0, 50),
                           span("lower_sharding_computation", 200, 400),
                           span("backend_compile_and_load", 300, 500)]),
    ("rest", [span("ptc.devmgr.ready_wait", 0, 50),
              span("lower_sharding_computation", 200, 500, line=1)]),
])
def test_each_state_takes_its_planted_idle_time(state, spans):
    # busy [0, 100) and [900, 1000): idle [100, 900), 80% of the solve
    tr = trace([[(0, 100), (900, 1000)]])
    out = S.split(tr, spans)
    want = dict.fromkeys(SHARES, 0.0)
    want[state] += 30.0
    want["rest"] += 80.0 - 30.0
    assert {k: out[k] for k in SHARES} == pytest.approx(want)
    check_sum(tr, out)


def test_precedence_keeps_the_shares_disjoint_and_means_over_chips():
    tr = trace([[(0, 100), (900, 1000)], [(0, 100)]])
    spans = [
        # chip 0: every state once, a build inside the dispatch
        span("ptc.devmgr.ready_wait", 100, 300),
        span("ptc.devmgr.batch_wait", 300, 400),
        span("ptc.devmgr.dispatch", 400, 700),
        span("lower_sharding_computation", 450, 550),
        span("backend_compile_and_load", 500, 600),
        span("lower_sharding_computation", 700, 800, line=2),  # main thread
        # chip 1, its own thread: overlapping spans, by precedence
        span("ptc.devmgr.dispatch", 100, 500, line=1, chip=1),
        span("ptc.devmgr.ready_wait", 300, 700, line=1, chip=1),
        span("ptc.devmgr.batch_wait", 0, 200, line=1, chip=1),
    ]
    out = S.split(tr, spans)
    chip0 = {"devmgr.build_idle": 15, "devmgr.batch_wait_idle": 10,
             "devmgr.dispatch_idle": 15, "runtime.ready_wait_idle": 20,
             "rest": 20}
    chip1 = {"devmgr.build_idle": 0, "devmgr.batch_wait_idle": 10,
             "devmgr.dispatch_idle": 30, "runtime.ready_wait_idle": 20,
             "rest": 30}
    assert {k: out[k] for k in SHARES} == pytest.approx(
        {k: (chip0[k] + chip1[k]) / 2 for k in SHARES})
    check_sum(tr, out)


def test_dump_round_trip(tmp_path):
    tr = trace([[(0, 100), (900, 1000)]])
    spans = [span("ptc.devmgr.ready_wait", 100, 300),
             span("ptc.devmgr.dispatch", 300, 600, ptc_ns=7, calls=2),
             span("lower_sharding_computation", 350, 450)]
    path = str(tmp_path / "t.json.gz")
    S.dump(path, tr, spans)
    tr2, spans2 = S.undump(path)
    assert S.split(tr2, spans2) == S.split(tr, spans)
    assert spans2 == spans


def test_clock_reads_offset_spread_and_drift():
    # the profiler's clock runs 1 ns per 1000 ahead of the native one
    spans = [span("ptc.devmgr.dispatch", 5000 + t + t // 1000, 0,
                  ptc_ns=t) for t in range(0, 100000, 1000)]
    c = S.clock(spans)
    assert c["pairs"] == 100
    assert c["offset_ns"] == 5049 or c["offset_ns"] == 5050
    assert c["drift_ns"] == 90
    assert S.clock([]) == {}


def test_sweep_close_shares_from_the_closed_arg():
    spans = [span("ptc.devmgr.batch_wait", 0, 1, closed="quiesced"),
             span("ptc.devmgr.batch_wait", 2, 3, closed="quiesced"),
             span("ptc.devmgr.batch_wait", 4, 5, closed="deadline"),
             span("ptc.devmgr.batch_wait", 6, 7, closed="full"),
             # a trace of a manager that does not say how it closed
             span("ptc.devmgr.batch_wait", 8, 9),
             span("ptc.devmgr.dispatch", 9, 10, closed="full")]
    assert S.sweep_close(spans) == {"quiesced": 50.0, "deadline": 25.0,
                                    "full": 25.0, "sweeps": 4}
    assert S.sweep_close(spans[4:]) == {}


def test_update_rows_per_useful_row_from_the_update_ops():
    """Lanes times rows of each panel update op run inside the solves
    (the whole-panel fusion of a full-height update, or one row block of
    a row-blocked one), per row the solves need; no other op counts."""
    cfg = {"dag": "panels", "N": 2048, "NB": 128}
    ops = [
        # solve 1: a full-height 8-lane wave, a lone lane, one row block
        # of a 4-lane wave
        ("%bitcast_subtract_fusion.3 = f32[8,2048,128]{2,1,0:T(8,128)} "
         "fusion(f32[2048,128]{1,0} %p), kind=kOutput, calls=%f.3", 0, 10),
        ("%bitcast_subtract_fusion = f32[2048,128]{1,0:T(8,128)} "
         "fusion(f32[2048,128]{1,0} %p), kind=kOutput, calls=%f.1", 20, 30),
        ("%subtract_select_fusion.2 = f32[4,128,128]{2,1,0:T(8,128)} "
         "fusion(f32[4,2048,128]{2,1,0} %a), kind=kOutput, calls=%f.2",
         40, 50),
        # a subtract without a dot, the factor's dot, a tile update
        ("%subtract_fusion = f32[8,2048,128]{2,1,0} fusion(), kind=kLoop",
         60, 70),
        ("%fusion.70 = f32[2048,128]{1,0} fusion(), kind=kOutput", 80, 90),
        ("%convolution_subtract_fusion = f32[128,256]{1,0} fusion(), "
         "kind=kOutput", 100, 110),
        # solve 2: one row block of 8 lanes; then one after the solves
        ("%subtract_select_fusion.2 = f32[8,256,128]{2,1,0} fusion(), "
         "kind=kOutput", 1100, 1200),
        ("%subtract_select_fusion.2 = f32[8,256,128]{2,1,0} fusion(), "
         "kind=kOutput", 2500, 2600),
    ]
    host = [("window", 0, 3000), ("solve", 0, 1000), ("solve", 1000, 2000)]
    tr = T.Trace({0: ops}, host, 1)
    useful = sum(j * (2048 - j * 128) for j in range(16))
    rows = 8 * 2048 + 2048 + 4 * 128 + 8 * 256
    assert S.update_rows(tr, cfg) == pytest.approx(rows / useful / 2)
    rep = S.report(tr, [], cfg=cfg)
    assert rep["update_rows"] == pytest.approx(rows / useful / 2)
    assert "update_rows" not in S.report(tr, [])
    # a tile cell, or a panel trace with no update op, reads nothing
    assert S.update_rows(tr, dict(cfg, dag="tiles")) is None
    assert S.update_rows(T.Trace({0: ops[3:6]}, host, 1), cfg) is None


def test_recorded_trace_without_manager_spans_is_all_rest():
    tr = T.Trace.from_file(FIXTURE, 1)
    spans = S.xplane_spans(FIXTURE, "ptc.devmgr.", threads=True)
    assert spans == []
    out = S.split(tr, spans)
    assert out["rest"] == pytest.approx(out["device.idle_share"])
    check_sum(tr, out)


def test_traced_benchmark_run_on_the_cpu(tmp_path, monkeypatch):
    """The tool's traced run of the tiny tile cell: the manager's spans
    count every task of the window's solves."""
    import jax
    from benchmark import harness
    from parsec_tpu.device import bench_utils
    bench = _load("bench_conftest", "tests", "bench", "conftest.py")
    monkeypatch.setattr(bench_utils, "hbm_bytes", lambda d: 8 << 30)
    root = bench.make_root(tmp_path / "root")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        tr, spans = S.traced_run(
            ["--workload", "tile", "--seed", "3", "--seconds", "0.5"],
            root=root, chip_check=lambda chips: jax.devices()[:chips],
            peaks=bench.FAKE_PEAKS)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is True
    rep = S.report(tr, spans)
    assert set(rep["spans"]) == {"ptc.devmgr.ready_wait",
                                 "ptc.devmgr.batch_wait",
                                 "ptc.devmgr.dispatch"}
    disp = [a for n, _, _, _, a in spans if n == "ptc.devmgr.dispatch"]
    cfg = json.load(open(os.path.join(root, "benchmark", "configs",
                                      "tile_cfg.json")))
    assert sum(a["tasks"] for a in disp) == \
        result["attempted"] * harness.tasks_of_solve(cfg)
    assert sum(a["calls"] for a in disp) == pytest.approx(
        result["attempted"] *
        result["metrics"]["devmgr.calls_per_solve"]["value"])
    assert len({a["tp"] for a in disp}) == result["attempted"]
    assert rep["clock"]["pairs"] == len(disp)
    # every sweep says how it closed
    sweeps = rep["spans"]["ptc.devmgr.batch_wait"]
    assert rep["sweep_close"]["sweeps"] == sweeps
    assert sum(rep["sweep_close"][k] for k in S.CLOSES) == \
        pytest.approx(100.0)
    # the CPU trace has no device plane: all of the solves is idle
    assert sum(rep["split"][k] for k in SHARES) == pytest.approx(100.0)
    assert rep["split"]["device.idle_share"] == pytest.approx(100.0)


def test_traced_run_reports_eager_gathers(tmp_path, monkeypatch):
    """The tool's line carries the window's eager gathers, summed from
    the dispatch spans' `eager` args, and the device's gather_shapes."""
    import jax
    from parsec_tpu.device import bench_utils
    bench = _load("bench_conftest", "tests", "bench", "conftest.py")
    monkeypatch.setattr(bench_utils, "hbm_bytes", lambda d: 8 << 30)
    root = bench.make_root(tmp_path / "root")
    stats, cfg = {}, {}
    with contextlib.redirect_stdout(io.StringIO()):
        tr, spans = S.traced_run(
            ["--workload", "panel", "--seed", "4", "--seconds", "0.5"],
            stats, cfg, root=root,
            chip_check=lambda chips: jax.devices()[:chips],
            peaks=bench.FAKE_PEAKS)
    rep = S.report(tr, spans, stats, cfg)
    assert (cfg["dag"], cfg["N"], cfg["NB"]) == ("panels", 1024, 128)
    # the CPU trace has no device plane: no update op to count
    assert rep["update_rows"] is None
    disp = [a for n, _, _, _, a in spans if n == "ptc.devmgr.dispatch"]
    assert all("eager" in a for a in disp)
    assert rep["eager_gathers"] == sum(a["eager"] for a in disp)
    assert 0 <= rep["eager_gathers"] <= stats["eager_gathers"]
    assert rep["gather_shapes"] == stats["gather_shapes"] >= 0
