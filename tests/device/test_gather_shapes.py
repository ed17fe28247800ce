"""The device manager's gathers build programs keyed by powers of two:
how a wave is split, by the byte cap, by batch_max or by timing, changes
which lanes go together, not which programs there are to build.

Solves here are driven by hand (the manager thread is not started):
each step waits until the engine has routed everything, pops the ready
tasks in priority order and hands them to the manager in passes of a
given size, so a split pattern is the same on every run."""
import threading
import time

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu import _native as N
from parsec_tpu.algos import build_potrf, build_potrf_panels
from parsec_tpu.data import TwoDimBlockCyclic
from parsec_tpu.device import TpuDevice
from parsec_tpu.device.bench_utils import install_device_tiles, residual_bound
from parsec_tpu.device.tpu import _StackRef, grouped_stack, local_tile_index


class Lowerings:
    """Programs JAX lowers, counted through jax.monitoring as the
    benchmark's CompileCounter counts them."""

    def __init__(self):
        import jax
        self.n = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if "jaxpr_to_mlir_module" in event:
            with self._lock:
                self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def _spd(n, seed):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n), dtype=np.float32)
    return m @ m.T + n * np.eye(n, dtype=np.float32)


def _pump(ctx, dev, ntasks, rng=None, lone=0.0, every=None):
    """Run `ntasks` device tasks by hand: at each step, once nothing is
    in flight, pop every ready task and hand them to the manager.  The
    ready tasks come in priority order, or in an order drawn from `rng`
    (as the engine's workers route them); with probability `lone` the
    first goes alone (as a release the manager catches early); `every`
    cuts the rest into passes of that many tasks (batch_max)."""
    t0 = dev.stats["tasks"]
    deadline = time.monotonic() + 120
    while dev.stats["tasks"] - t0 < ntasks:
        assert time.monotonic() < deadline, "the solve stalled"
        while ctx.tasks_in_flight() > 0:
            time.sleep(0.0002)
        ready = []
        while True:
            t = ctx.device_pop(dev.qid, 0)
            if not t:
                break
            ready.append(t)
        if not ready:
            time.sleep(0.0005)
            continue
        ready.sort(key=lambda t: -N.lib.ptc_task_priority(t))
        if rng is not None:
            rng.shuffle(ready)
        cuts = [0]
        if len(ready) > 1 and rng is not None and rng.random() < lone:
            cuts.append(1)
        if every:
            cuts += range(cuts[-1] + every, len(ready), every)
        cuts.append(len(ready))
        for i, j in zip(cuts, cuts[1:]):
            if j - i == 1:
                dev._dispatch(ready[i])
            elif j > i:
                dev._dispatch_batch(ready[i:j])


class Solver:
    """One rank holding A on a hand-driven device."""

    def __init__(self, ctx, dag, n, nb, lanes=None):
        self.ctx, self.dag, self.n, self.nb = ctx, dag, n, nb
        panels = dag == "panels"
        self.A = TwoDimBlockCyclic(n, n, n if panels else nb, nb,
                                   dtype=np.float32)
        self.A.register(ctx, "A")
        self.dev = TpuDevice(ctx, autostart=False, prefetch=False,
                             cache_bytes=1 << 32)
        if lanes:
            # a wave's PK, PJ in and PJ out panels cap its chunks
            self.dev.batch_max_bytes = lanes * (3 * n * nb + 1) * 4
        nt = n // nb
        self.ntasks = (nt + nt * (nt - 1) // 2 if panels else
                       nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6)

    def solve(self, seed, **pump):
        """Factor a fresh SPD matrix; returns it."""
        import jax
        a = _spd(self.n, seed)
        nb = self.nb
        tiles = [a[:, j * nb:(j + 1) * nb] if self.dag == "panels" else
                 a[m * nb:(m + 1) * nb, j * nb:(j + 1) * nb]
                 for m, j in local_tile_index(self.A)]
        install_device_tiles(self.dev, self.A, jax.device_put(
            np.stack(tiles), self.dev.device))
        build = build_potrf_panels if self.dag == "panels" else build_potrf
        tp = build(self.ctx, self.A, dev=self.dev)
        tp.run()
        _pump(self.ctx, self.dev, self.ntasks, **pump)
        tp.wait()
        return a

    def factor(self):
        self.dev.flush()
        if self.dag == "panels":
            return np.tril(np.concatenate(
                [self.A.tile(0, j) for j in range(self.A.nt)], axis=1))
        return np.tril(self.A.to_dense())


def _check_factor(s, a):
    """The factor against jnp.linalg.cholesky at HIGHEST, within the
    repo's fp32 residual bound."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jnp.linalg.cholesky(jnp.asarray(a)))
    err = np.abs(s.factor() - ref).max() / np.abs(ref).max()
    assert err <= residual_bound(s.n), err


# (DAG, N, NB, chunk lanes): 64 panels cut into chunks of 8 lanes, as
# the panel cell's waves are; the tile cell's DAG of 16 x 16 tiles
DAGS = [("panels", 2048, 32, 8), ("tiles", 2048, 128, None)]


@pytest.mark.parametrize("dag,n,nb,lanes", DAGS)
def test_routing_order_builds_nothing_after_the_first_solve(dag, n, nb,
                                                            lanes):
    """Whole waves whose tasks come in a different order each solve, as
    the engine's workers route them: after the first solve no solve
    lowers a program.  (Cutting capped waves at fixed multiples of the
    pop order built new gathers in every solve here.)"""
    count = Lowerings()
    try:
        with pt.Context(nb_workers=2) as ctx:
            s = Solver(ctx, dag, n, nb, lanes)
            s.solve(0)
            built = count.n
            for seed in range(1, 5):
                a = s.solve(seed, rng=np.random.default_rng(seed))
                assert count.n == built, \
                    f"solve {seed} built {count.n - built} programs"
            _check_factor(s, a)
            s.dev.stop()
    finally:
        count.close()


@pytest.mark.parametrize("pump", [
    {"every": 3}, {"every": 5}, {"every": 64}, {"lone": 0.2}],
    ids=["batch_max3", "batch_max5", "batch_max64", "lone_first"])
def test_split_waves_gather_in_power_of_two_shapes(pump):
    """Panel waves split by batch_max, or with their first task caught
    alone: every eager gather program is keyed by power-of-two row
    counts (or the generator's stack), and the factor is right."""
    with pt.Context(nb_workers=2) as ctx:
        s = Solver(ctx, "panels", 2048, 32, 8)
        for seed in range(3):
            a = s.solve(seed, rng=np.random.default_rng(seed), **pump)
        nt = 2048 // 32
        keys = s.dev._gather_keys
        assert s.dev.stats["gather_shapes"] == len(keys)
        for key in keys:
            rows = [k[0] for k in key[1:] if isinstance(k, tuple)]
            assert all(r & (r - 1) == 0 or r == nt for r in rows), key
        _check_factor(s, a)
        s.dev.stop()


def _concat_gather(jnp, ents, bucket):
    """The gather of a mixed wave flow as it was before its programs
    were bounded: a take per source stack, one stack of the loose tiles,
    a concatenation and a take into lane order."""
    by_stack, loose = {}, []
    for pos, e in enumerate(ents):
        if isinstance(e, _StackRef):
            by_stack.setdefault(id(e.stack), (e.stack, []))[1] \
                .append((pos, e.idx))
        else:
            loose.append((pos, e))
    parts, order = [], []
    for stack, rows in by_stack.values():
        parts.append(jnp.take(stack, jnp.asarray([r for _, r in rows],
                                                 dtype=jnp.int32), axis=0))
        order.extend(p for p, _ in rows)
    if loose:
        parts.append(jnp.stack([a for _, a in loose]))
        order.extend(p for p, _ in loose)
    cat = jnp.concatenate(parts)
    perm = [0] * len(ents)
    for row, pos in enumerate(order):
        perm[pos] = row
    perm += [perm[0]] * (bucket - len(perm))
    return jnp.take(cat, jnp.asarray(perm, dtype=jnp.int32), axis=0)


def _sources(seed, sizes, tile=(16, 8)):
    """Stacks of the given row counts and four loose tiles, each row a
    distinct random tile."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    stacks = [jnp.asarray(rng.standard_normal((s,) + tile,
                                              dtype=np.float32))
              for s in sizes]
    loose = [jnp.asarray(rng.standard_normal(tile, dtype=np.float32))
             for _ in range(4)]
    return stacks, loose


# (lane -> ("s", stack, row) | ("l", loose tile)), bucket: the largest
# stack has room for the rest (a copy of it takes them), or none
MIXES = [
    ([("s", 0, 1), ("s", 1, 0), ("s", 0, 3), ("s", 1, 2)], 4),
    ([("s", 1, 7), ("s", 0, 0), ("l", 2), ("s", 2, 1), ("s", 0, 2)], 8),
    ([("l", 0), ("s", 2, 0), ("l", 3), ("s", 0, 3), ("s", 1, 5),
      ("s", 1, 6), ("l", 1)], 8),
    ([("s", 1, i) for i in range(8)] + [("s", 0, 1), ("l", 0)], 16),
]


@pytest.mark.parametrize("mix,bucket", MIXES)
def test_folded_lanes_are_bit_identical(mix, bucket):
    """The lanes gathered from the folded stack are bit for bit those
    of the concatenating gather, padding lanes included (entry 0
    repeated), and each program the fold ran is keyed by the shapes of
    two stacks, the one built having a power of two rows or those of a
    source."""
    import jax.numpy as jnp
    stacks, loose = _sources(7, (4, 8, 2))
    ents = [_StackRef(stacks[e[1]], e[2]) if e[0] == "s" else loose[e[1]]
            for e in mix]
    seen = set()
    got = np.asarray(grouped_stack(jnp, ents, bucket, seen))
    want = np.asarray(_concat_gather(jnp, ents, bucket))
    assert got.shape == (bucket, 16, 8)
    assert got.tobytes() == want.tobytes()
    assert {k[0] for k in seen} <= {"copy", "zeros", "into", "stack"}
    for key in seen:
        rows = key[1][0]
        assert rows & (rows - 1) == 0 or rows in (4, 8, 2), key


def test_padding_reaches_no_counted_lane():
    """Rows no entry names are poisoned: none of them reaches a lane,
    counted or padding, and the padding lanes repeat entry 0."""
    import jax.numpy as jnp
    stacks, loose = _sources(3, (8, 4))
    poison = [np.full((s.shape[0], 16, 8), np.nan, np.float32)
              for s in stacks]
    mix = [("s", 1, 3), ("s", 0, 5), ("l", 0), ("s", 0, 6), ("s", 1, 1)]
    for kind, *at in mix:
        if kind == "s":
            poison[at[0]][at[1]] = np.asarray(stacks[at[0]][at[1]])
    pstacks = [jnp.asarray(p) for p in poison]
    ents = [_StackRef(pstacks[e[1]], e[2]) if e[0] == "s" else loose[e[1]]
            for e in mix]
    out = np.asarray(grouped_stack(jnp, ents, 8))
    assert not np.isnan(out).any()
    for lane, e in enumerate(mix):
        want = (poison[e[1]][e[2]] if e[0] == "s"
                else np.asarray(loose[e[1]]))
        assert out[lane].tobytes() == want.tobytes()
    for lane in range(len(mix), 8):
        assert out[lane].tobytes() == out[0].tobytes()
