"""Transfer-economics harness smoke: tools/testbandwidth.py must run at
small sizes entirely on loopback and emit schema-valid JSON.  The full
sweep is `make bench-comm`; this validates the contract CI relies on."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_HARNESS = os.path.join(_REPO, "tools", "testbandwidth.py")

_SIZE_KEYS = {"size_bytes", "setup_ms", "per_transfer_ms",
              "per_transfer_ms_all", "gbps"}
_FIT_KEYS = {"fixed_overhead_us", "per_byte_ns", "eff_gbps", "r2",
             "npoints"}
_TUNE_KEYS = {"eager_limit", "chunk_size", "inflight", "rtt_ns",
              "memcpy_bps", "chunks_sent", "chunks_recv",
              "eager_adaptive"}


def _run_harness(tmp_path, paths, sizes, port):
    out = tmp_path / "econ.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PTC_PORT"] = str(port)
    cmd = [sys.executable, _HARNESS, "--paths", paths, "--sizes", sizes,
           "--hops", "4", "--reps", "2", "--json", str(out)]
    res = subprocess.run(cmd, cwd=_REPO, env=env, capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
    with open(out) as f:
        return json.load(f)


def _check_path_report(rep, expect_sizes, expect_chunks=False):
    assert set(rep) >= {"sizes", "fit", "tunables"}, rep.keys()
    assert [r["size_bytes"] for r in rep["sizes"]] == expect_sizes
    for row in rep["sizes"]:
        assert _SIZE_KEYS <= set(row), row.keys()
        assert row["per_transfer_ms"] > 0
        assert row["setup_ms"] >= 0
        assert len(row["per_transfer_ms_all"]) == 2  # --reps 2
    if len(expect_sizes) >= 2:
        assert _FIT_KEYS <= set(rep["fit"]), rep["fit"]
        assert rep["fit"]["npoints"] == len(expect_sizes)
    else:
        assert rep["fit"] is None  # a line needs two points
    assert _TUNE_KEYS <= set(rep["tunables"]), rep["tunables"]
    if expect_chunks:
        assert rep["tunables"]["chunks_recv"] > 0, rep["tunables"]


def test_harness_schema_host_paths(tmp_path):
    """eager + rendezvous sweeps on loopback; the rdv path is driven
    through the chunk protocol by a small chunk_size via its own knob
    defaults (64 KiB payload > 1 MiB default chunk is false, so check
    chunks only when forced — here we validate schema + monotone fit
    plumbing)."""
    doc = _run_harness(tmp_path, "eager,rdv", "4096,65536", port=31900)
    assert doc["bench"] == "transfer_economics"
    assert set(doc["paths"]) == {"eager", "rdv"}
    for p in ("eager", "rdv"):
        _check_path_report(doc["paths"][p], [4096, 65536])
    # the adaptive probe must report the engine's derived threshold
    ae = doc["adaptive_eager"]
    assert {"derived_eager_limit", "rtt_ns", "memcpy_bps"} <= set(ae), ae
    assert 16 * 1024 <= ae["derived_eager_limit"] <= 16 * 1024 * 1024


@pytest.mark.slow
def test_harness_schema_device_path(tmp_path):
    """PK_DEVICE path smoke (slow: device bring-up per process pair).
    2 MiB payload > default chunk_size, so the pipelined chunk protocol
    must carry it and the JSON must say so."""
    doc = _run_harness(tmp_path, "device", "2097152", port=31910)
    rep = doc["paths"]["device"]
    _check_path_report(rep, [2097152], expect_chunks=True)
    assert rep["device_stats"] is not None
    assert rep["device_stats"]["dp_sends"] > 0, rep["device_stats"]
