"""Benchmark helpers: on-device data generation, device memory budget and
residual checks.

DPLASMA-style benchmarking generates the matrix in place (dplrnt) and
verifies with a residual.  These helpers do the same on the TPU: tiles are
generated by a jitted kernel directly into the device copy cache, and a
factorization is checked two ways that need little memory
(potrf_residual, potrf_host_check), both against the generator itself.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from .. import _native as N
from .tpu import TpuDevice, _StackRef, _get_jitted, local_tile_index

# (nb, N, seed-independent) -> jitted generator / (mt, nt, nb) -> residual.
# Keyed by static shape params so repeated bench reps reuse ONE executable
# instead of re-tracing fresh closures every call.
_GEN_CACHE: Dict[tuple, object] = {}
_RESID_CACHE: Dict[tuple, object] = {}


def _spd_block(m, n, sd, nb, Nn):
    """One nb x nb block (m, n) of the symmetric diagonally-dominant
    (hence SPD) test matrix: keyed by the UNORDERED pair so (n, m) is the
    transpose, 2N added on the diagonal.  The single definition both the
    tiled and the panel generators close over — their outputs must be
    byte-identical block for block."""
    import jax
    import jax.numpy as jnp
    lo = jnp.minimum(m, n)
    hi = jnp.maximum(m, n)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(sd), lo), hi)
    t = jax.random.normal(key, (nb, nb), jnp.float32)
    # symmetric across the diagonal: (n, m) gets the transpose
    t = jnp.where(m > n, t, jnp.where(m < n, t.T, (t + t.T) * 0.5))
    diag = jnp.where(m == n, 2.0 * Nn, 0.0)
    return t + diag * jnp.eye(nb, dtype=jnp.float32)


def generate_spd_on_device(dev: TpuDevice, coll, seed: int = 0):
    """Generate the SPD test matrix directly on the TPU and install the
    tiles in the device copy cache.  Returns the stacked (ntiles, nb, nb)
    device array."""
    import jax
    import jax.numpy as jnp
    nb = coll.mb
    Nn = coll.M
    tiles = local_tile_index(coll)
    ms = jax.device_put(np.array([m for m, _ in tiles], dtype=np.int32),
                        dev.device)
    ns = jax.device_put(np.array([n for _, n in tiles], dtype=np.int32),
                        dev.device)

    gen = _GEN_CACHE.get((nb, Nn))
    if gen is None:
        gen = jax.jit(jax.vmap(
            lambda m, n, sd: _spd_block(m, n, sd, nb, Nn),
            in_axes=(0, 0, None)))
        _GEN_CACHE[(nb, Nn)] = gen
    stacked = gen(ms, ns, seed)
    install_device_tiles(dev, coll, stacked)
    return stacked


def generate_spd_panels_on_device(dev: TpuDevice, coll, seed: int = 0):
    """Panel-collection variant of generate_spd_on_device: `coll` is one
    block row of N x nb column panels (TwoDimBlockCyclic(N, N, N, nb)).
    Generates the SAME symmetric diagonally-dominant matrix the tiled
    generator would (same per-block fold_in keys), as the stacked
    (local panels, N, nb) panels this rank stores, and installs them in
    the device cache."""
    import jax
    nb = coll.nb
    Nn = coll.M
    assert coll.mt == 1 and coll.mb == Nn

    gen = _GEN_CACHE.get((nb, Nn, "panels"))
    if gen is None:
        gen = jax.jit(jax.vmap(lambda j, sd: _spd_column(sd, j, nb, Nn),
                               in_axes=(0, None)))
        _GEN_CACHE[(nb, Nn, "panels")] = gen
    js = np.array([n for _, n in local_tile_index(coll)], dtype=np.int32)
    stacked = gen(jax.device_put(js, dev.device), seed)
    install_device_tiles(dev, coll, stacked)
    return stacked


def install_device_tiles(dev: TpuDevice, coll, stacked):
    """Install stacked[i] as the device-resident copy of local tile i.
    Entries are lazy stack slices: nothing is sliced out on device unless
    a host sync or an unbatched consumer asks — batched consumers gather
    straight from the generation stack."""
    nbytes = coll.mb * coll.nb * coll.dtype.itemsize
    for i, (m, n) in enumerate(local_tile_index(coll)):
        d = coll.data_of(m, n)
        cptr = N.lib.ptc_data_host_copy(d._ptr)
        uid = dev._copy_uid(cptr)
        ver = N.lib.ptc_copy_version(cptr)
        dev._cache_put(uid, ver, _StackRef(stacked, i), nbytes)


def install_device_segments(dev: TpuDevice, vcoll, stacked):
    """1-D collection variant of install_device_tiles: stacked[i] becomes
    the device-resident copy of this rank's i-th local segment (ascending
    key order).  Used to pre-stage index/metadata collections so their
    per-wave gathers ride the fused (stack, idx) path."""
    nbytes = vcoll.nb * vcoll.dtype.itemsize
    i = 0
    for k in range(vcoll.nt):
        if vcoll.rank_of(k) != vcoll.myrank:
            continue
        d = vcoll.data_of(k)
        cptr = N.lib.ptc_data_host_copy(d._ptr)
        uid = dev._copy_uid(cptr)
        ver = N.lib.ptc_copy_version(cptr)
        dev._cache_put(uid, ver, _StackRef(stacked, i), nbytes)
        i += 1


def wait_device_tiles(dev: TpuDevice, coll) -> None:
    """Block until every local tile's current device value is concrete.
    Blocks on the underlying batch stacks directly — no slicing, no
    gather copies — so it is the cheap end-of-timed-region sync."""
    stacks = {}
    for m, n in local_tile_index(coll):
        d = coll.data_of(m, n)
        cptr = N.lib.ptc_data_host_copy(d._ptr)
        uid = dev._copy_uid(cptr)
        ver = N.lib.ptc_copy_version(cptr)
        ent = dev._cache_ent(uid, ver)
        if ent is None:
            continue
        a = ent.arr
        obj = a.stack if isinstance(a, _StackRef) else a
        stacks[id(obj)] = obj
    for s in stacks.values():
        s.block_until_ready()


def hbm_bytes(device) -> int:
    """Device memory a program may use: PJRT's memory_stats()
    ["bytes_limit"].  A device that does not report it is an error, not a
    guess."""
    stats = device.memory_stats() or {}
    if not stats.get("bytes_limit"):
        raise RuntimeError(f"{device.platform} device {device.device_kind!r}"
                           " reports no memory_stats()['bytes_limit']")
    return int(stats["bytes_limit"])


def spotrf_budget(hbm: int, local_bytes: int):
    """(tile-cache bytes, per-call batch byte cap) for a factorization
    holding `local_bytes` of fp32 matrix on a device with `hbm` usable
    bytes.  The generator's stack (this rank's share of the matrix) plus
    batch transients and XLA workspace take their share; a cache budget
    above HBM would never evict dead tile versions.  The per-call cap
    keeps one call's in+out stacks beside the matrix: bigger calls mean
    fewer device calls per update wave."""
    headroom = int(hbm - local_bytes - (3 << 30))
    return max(2 << 30, headroom), max(1 << 30, headroom // 3)


def spotrf_device(ctx, coll, jax_device=None) -> TpuDevice:
    """The TpuDevice a spotrf run uses: memory budget from the device's
    bytes_limit (spotrf_budget), wide batches (whole waves in ONE stack,
    so consumers hit the single-take gather path), waves accumulated for
    up to 5 ms while the engine still routes tasks."""
    import jax
    jax_device = jax_device or jax.devices()[0]
    local = len(local_tile_index(coll)) * coll.mb * coll.nb \
        * coll.dtype.itemsize
    cache_bytes, batch_bytes = spotrf_budget(hbm_bytes(jax_device), local)
    dev = TpuDevice(ctx, jax_device=jax_device, cache_bytes=cache_bytes)
    dev.batch_max = 512
    dev.batch_wait_ms = 5.0
    dev.batch_max_bytes = batch_bytes
    return dev


def residual_bound(n: int) -> float:
    """The fp32 bound both potrf checks are held to: sqrt(N) * eps32, the
    probabilistic backward-error scale of an N-term fp32 computation
    (Higham & Mary, SIAM J. Sci. Comput. 2019).  A factorization whose
    products ran in bf16 (eps 2**-8) misses it by two orders of
    magnitude."""
    return float(np.sqrt(n) * np.finfo(np.float32).eps)


def _spd_column(sd, j, nb, Nn):
    """Block column j (N x nb) of the generator's matrix."""
    import jax
    import jax.numpy as jnp
    ms = jnp.arange(Nn // nb, dtype=jnp.int32)
    blocks = jax.vmap(lambda m: _spd_block(m, j, sd, nb, Nn))(ms)
    return blocks.reshape(Nn, nb)


def _owner(parts, m, n):
    """The (dev, coll) of `parts` whose rank stores tile (m, n)."""
    for dev, coll in parts:
        if coll.rank_of(m, n) == coll.myrank:
            return dev, coll
    raise KeyError(f"no part stores tile ({m}, {n})")


def current_device_tile(dev: TpuDevice, coll, m, n, rows=slice(None)):
    """The current value of `rows` of local tile (m, n) as a device
    array: from its device mirror (sliced straight out of a batch stack,
    no copy of the whole tile), or the host copy staged in when it has
    none."""
    d = coll.data_of(m, n)
    cptr = N.lib.ptc_data_host_copy(d._ptr)
    uid = dev._copy_uid(cptr)
    ent = dev._cache_ent(uid, N.lib.ptc_copy_version(cptr))
    if ent is None or ent.raw:
        return dev._jax.device_put(coll.tile(m, n)[rows], dev.device)
    a = ent.arr
    return a.stack[a.idx, rows] if isinstance(a, _StackRef) else a[rows]


def _column(parts, n):
    """(device, block column n of the stored matrix, N x nb)."""
    import jax
    dev, coll = _owner(parts, 0, n)
    if coll.mt == 1:
        return dev, current_device_tile(dev, coll, 0, n)
    tiles = [jax.device_put(current_device_tile(*_owner(parts, m, n), m, n),
                            dev.device) for m in range(coll.mt)]
    return dev, jax.numpy.concatenate(tiles)


def potrf_residual(parts, seed: int, nvec: int = 4) -> float:
    """max over `nvec` seeded random x of
    ||L (L^T x) - A x|| / (||A||_1 ||x||), on the device at
    Precision.HIGHEST, one block column at a time: O(N x nb) memory
    beside the factored matrix.  `parts` is [(dev, coll)], one per rank
    holding part of the factored matrix (single rank: [(dev, A)]); L is
    the lower triangle of what the factorization left there, A is
    regenerated from `seed`.  Each device accumulates its own columns'
    share; the shares are summed on the host in float64."""
    import jax
    import jax.numpy as jnp
    coll = parts[0][1]
    nb, Nn = coll.nb, coll.M
    key = ("resid", nb, Nn, nvec)
    step = _RESID_CACHE.get(key)
    if step is None:
        hi = jax.lax.Precision.HIGHEST

        def step(lcol, x, j, sd, z, ax, anorm):
            rows = jnp.arange(Nn, dtype=jnp.int32)[:, None]
            cols = j * nb + jnp.arange(nb, dtype=jnp.int32)[None, :]
            lcol = jnp.where(rows >= cols, lcol, jnp.zeros((), lcol.dtype))
            a = _spd_column(sd, j, nb, Nn)
            xj = jax.lax.dynamic_slice(x, (j * nb, 0), (nb, nvec))
            z = z + jnp.dot(lcol, jnp.dot(lcol.T, x, precision=hi),
                            precision=hi)
            ax = ax + jnp.dot(a, xj, precision=hi)
            anorm = jnp.maximum(anorm, jnp.max(jnp.sum(jnp.abs(a), 0)))
            return z, ax, anorm

        step = jax.jit(step)
        _RESID_CACHE[key] = step
    x = np.random.default_rng(seed + 1).standard_normal(
        (Nn, nvec)).astype(np.float32)
    acc = {}   # device -> [x, z, ax, anorm]
    for n in range(coll.nt):
        dev, lcol = _column(parts, n)
        if dev.device not in acc:
            acc[dev.device] = jax.device_put(
                [x, np.zeros_like(x), np.zeros_like(x), np.float32(0)],
                dev.device)
        xs, z, ax, anorm = acc[dev.device]
        acc[dev.device][1:] = step(lcol, xs, np.int32(n), np.int32(seed),
                                   z, ax, anorm)
    z = sum(np.asarray(a[1], np.float64) for a in acc.values())
    ax = sum(np.asarray(a[2], np.float64) for a in acc.values())
    anorm = max(float(a[3]) for a in acc.values())
    r = np.linalg.norm(z - ax, axis=0) / (anorm * np.linalg.norm(x, axis=0))
    return float(r.max())


def potrf_host_check(parts, seed: int) -> float:
    """Componentwise backward error of a few blocks of L L^T, in float64
    on the host: max |L L^T - A| / (|L| |L^T|) over the nb x nb blocks
    (I, J) for I, J in {first, middle, last} block row, J <= I.  Pulls
    three block rows of L (3 x nb x N) and regenerates A's blocks from
    `seed` — an answer independent of the device's matmul precision."""
    import jax
    coll = parts[0][1]
    nb, Nn = coll.nb, coll.M
    nt = Nn // nb
    gen = _GEN_CACHE.get((nb, Nn, "block"))
    if gen is None:
        gen = jax.jit(lambda m, n, sd: _spd_block(m, n, sd, nb, Nn))
        _GEN_CACHE[(nb, Nn, "block")] = gen

    def lblock(m, n):
        if coll.mt == 1:
            dev, c = _owner(parts, 0, n)
            b = current_device_tile(dev, c, 0, n,
                                    slice(m * nb, (m + 1) * nb))
        else:
            b = current_device_tile(*_owner(parts, m, n), m, n)
        b = np.asarray(b, np.float64)
        return np.tril(b) if m == n else b

    rows = sorted({0, nt // 2, nt - 1})
    L = {i: np.concatenate([lblock(i, n) for n in range(i + 1)], axis=1)
         for i in rows}
    worst = 0.0
    for i in rows:
        for j in rows:
            if j > i:
                continue
            w = (j + 1) * nb
            li, lj = L[i][:, :w], L[j][:, :w]
            a = np.asarray(gen(np.int32(i), np.int32(j), np.int32(seed)),
                           np.float64)
            err = np.abs(li @ lj.T - a) / (np.abs(li) @ np.abs(lj).T)
            worst = max(worst, float(err.max()))
    return worst
