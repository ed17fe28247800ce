"""Multi-rank launcher: the mpirun analog for loopback SPMD jobs.

    python -m parsec_tpu.comm.launch -n 4 [--port BASE] script.py [args...]

Spawns N copies of `script.py` with PTC_RANK / PTC_WORLD / PTC_PORT set;
the script calls `parsec_tpu.comm.init(ctx)` to join the mesh.  Mirrors
the reference's `${MPI_TEST_CMD_LIST} <nproc>` test template
(tests/CMakeLists.txt:41-57, SURVEY.md §4).

Only one process may hold a TPU, so the launched ranks run JAX on the CPU
backend (JAX_PLATFORMS=cpu).  Ranks that drive chips are colocated in ONE
process instead (`Context.comm_set_colocated`; chip_smoke.py --chips 4).
"""
import argparse
import os
import random
import socket
import subprocess
import sys


def _free_port_base(n: int) -> int:
    for _ in range(64):
        base = random.randint(20000, 55000)
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port range found")


def main(argv=None):
    ap = argparse.ArgumentParser(prog="parsec_tpu.comm.launch")
    ap.add_argument("-n", "--np", type=int, required=True,
                    help="number of ranks")
    ap.add_argument("--port", type=int, default=0,
                    help="base TCP port (default: pick a free range)")
    ap.add_argument("script")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    opts = ap.parse_args(argv)

    port = opts.port or _free_port_base(opts.np)
    procs = []
    for r in range(opts.np):
        env = dict(os.environ, PTC_RANK=str(r), PTC_WORLD=str(opts.np),
                   PTC_PORT=str(port), JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(
            [sys.executable, opts.script, *opts.args], env=env))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    if rc:
        for p in procs:
            if p.poll() is None:
                p.terminate()
    return rc


if __name__ == "__main__":
    sys.exit(main())
