"""The chip entry points refuse to run without a TPU: chip_smoke.py and
the headline bench.py exit non-zero and print no JSON result line — no
CPU fallback.  Each runs in a subprocess pinned to the CPU backend, which
never loads the TPU library."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(r):
    assert r.returncode != 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert not any(line.lstrip().startswith("{")
                   for line in r.stdout.splitlines()), r.stdout[-2000:]


def test_chip_smoke_fails_without_tpu(tmp_path):
    r = _run([os.path.join(ROOT, "chip_smoke.py")], tmp_path)
    _no_result(r)
    assert "no TPU" in r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Copied out of the repo, the script cannot import the runtime."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    _no_result(_run(["chip_smoke.py"], tmp_path))


def test_bench_headline_fails_without_tpu(tmp_path):
    r = _run([os.path.join(ROOT, "bench.py")], tmp_path)
    _no_result(r)
    assert "needs a TPU" in r.stderr
