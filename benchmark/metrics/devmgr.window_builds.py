"""Programs built inside the window, per timed solve.

From the benchmark's own CompileCounter: each timed solve's
`built["lowered"]`, the programs JAX lowered while it ran, whether then
loaded from the compile cache or compiled.  Mean over the run's timed
solves; none when there are none.  Warm-up should leave nothing to
build, so anything above 0 is set-up work paid inside the window.
"""


def read(run):
    if not run.solves:
        return None
    return sum(s.built["lowered"] for s in run.solves) / len(run.solves)
