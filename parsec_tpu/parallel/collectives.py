"""Sequence-sharding collectives as library functions.

SURVEY.md §5 requires ring / all-gather / P2P-permute sequence-sharding
collectives over ICI as library operations (the reference's analogs are the
chain/binomial broadcast topologies of parsec/remote_dep.c:39-47 and the
redistribute all-to-all of redistribute.jdf).  Each helper here wraps the
XLA collective in a `shard_map` so callers hand in a *globally sharded*
array and get one back — XLA lowers the inner op onto ICI.

ISSUE 6 adds the dispatching front door (`all_reduce` / `reduce_scatter`
/ `all_gather` / `broadcast`): when a live multi-rank Context is passed,
the op runs as a RUNTIME-NATIVE ptc_coll_* taskpool (parsec_tpu.comm.
coll — tile slices stream into the reduction chunk-granularly, topology
per the transfer-economics selector); otherwise it falls back to the
shard_map/XLA path over `mesh` (whole-array, bulk-synchronous), or to
the trivial local semantics with neither.  Both paths produce bit-exact
results for bit-exact-reducible data (e.g. integer-valued float32 sums).
"""
from functools import partial
from typing import Optional

import numpy as np

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def ring_permute(x, mesh: Mesh, axis: str, shift: int = 1, shard_dim: int = 0):
    """Rotate shards one step around the `axis` ring (chain topology:
    parsec/remote_dep.c:43 `remote_dep_bcast_chain_child`).  Device i's
    shard moves to device (i+shift) mod n via `lax.ppermute` (ICI
    neighbor traffic on TPU)."""
    n = mesh.shape[axis]
    spec = [None] * x.ndim
    spec[shard_dim] = axis
    pspec = P(*spec)

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=pspec, out_specs=pspec)
    def _f(xs):
        return lax.ppermute(xs, axis, _ring_perm(n, shift))

    return _f(x)


def seq_all_gather(x, mesh: Mesh, axis: str, shard_dim: int = 0):
    """Gather the sequence-sharded dim onto every device (star topology
    analog: parsec/remote_dep.c:47).  Returns the replicated full array."""
    spec = [None] * x.ndim
    spec[shard_dim] = axis
    in_spec = P(*spec)
    out_spec = P(*([None] * x.ndim))

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=in_spec, out_specs=out_spec)
    def _f(xs):
        return lax.all_gather(xs, axis, axis=shard_dim, tiled=True)

    return _f(x)


def seq_reduce_scatter(x, mesh: Mesh, axis: str, shard_dim: int = 0):
    """Sum-reduce a replicated array and scatter shards along `shard_dim`
    (the tree-reduction taskpools of the reference's
    parsec/data_dist/matrix/reduce_col.jdf, fused into one XLA op)."""
    spec = [None] * x.ndim
    out_sp = list(spec)
    out_sp[shard_dim] = axis

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=P(*spec), out_specs=P(*out_sp))
    def _f(xs):
        return lax.psum_scatter(xs, axis, scatter_dimension=shard_dim,
                                tiled=True)

    return _f(x)


def seq_all_to_all(x, mesh: Mesh, axis: str, split_dim: int, concat_dim: int):
    """Reshard: split `split_dim` across `axis` while gathering the
    previously sharded `concat_dim` — one XLA all-to-all.  This is the
    reference's generic redistribute (redistribute.jdf) restricted to the
    uniform case, and the core move of Ulysses attention."""
    in_sp = [None] * x.ndim
    in_sp[concat_dim] = axis
    out_sp = [None] * x.ndim
    out_sp[split_dim] = axis

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=P(*in_sp), out_specs=P(*out_sp))
    def _f(xs):
        return lax.all_to_all(xs, axis, split_axis=split_dim,
                              concat_axis=concat_dim, tiled=True)

    return _f(x)


# --------------------------------------------------------------------
# dispatching collectives: runtime-native when a Context is live,
# shard_map/XLA otherwise (ISSUE 6 tentpole wiring)
# --------------------------------------------------------------------

def _runtime_live(ctx) -> bool:
    """A Context qualifies for the runtime-native ptc_coll_* path when
    it is live, multi-rank and its comm engine is up."""
    return (ctx is not None and getattr(ctx, "comm_enabled", False)
            and max(1, ctx.nodes) > 1)


def _stacked(x, mesh: Mesh, axis: str):
    n = mesh.shape[axis]
    x = np.asarray(x) if not hasattr(x, "sharding") else x
    if x.shape[0] != n:
        raise ValueError(
            f"XLA collective fallback wants per-device contributions "
            f"stacked on dim 0 (length {n} for mesh axis {axis!r}); "
            f"got shape {x.shape}")
    return x


def all_reduce(x, ctx=None, mesh: Optional[Mesh] = None,
               axis: str = "sp", op: str = "sum",
               topo: Optional[str] = None, tp=None):
    """Elementwise-reduce per-rank contributions; replicated result.

    Runtime path (`ctx` live + multi-rank): `x` is THIS rank's local
    contribution; returns the cross-rank reduction (same shape) via the
    streamed ptc_coll_* task classes.  With `tp` (a live taskpool the
    caller is about to run), the chains emit IN-POOL instead of as a
    standalone bulk-synchronous pool (ptc-shard): the returned array is
    zero-filled now and written by the fan-out sinks as the caller's
    pool executes — the collective overlaps the pool's other work (see
    comm.coll.all_reduce_into / restore_topology).  XLA path (`mesh`):
    `x` stacks the contributions on dim 0 (one per device of `axis`);
    returns their reduction via shard_map+psum.  Neither: local
    semantics (`x` is the only contribution)."""
    if _runtime_live(ctx):
        if tp is not None:
            from ..comm.coll import all_reduce_into
            return all_reduce_into(ctx, tp, np.asarray(x), op=op,
                                   topo=topo)
        from ..comm.coll import all_reduce as _ar
        return _ar(ctx, np.asarray(x), op=op, topo=topo)
    if mesh is not None:
        if op != "sum":
            raise NotImplementedError(
                "XLA fallback all_reduce supports op='sum'")
        xs = _stacked(x, mesh, axis)
        nd = xs.ndim
        out_spec = P(*([None] * (nd - 1)))

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=P(axis),
                 out_specs=out_spec)
        def _f(s):
            return lax.psum(s[0], axis)

        return _f(xs)
    return np.asarray(x).copy()


def reduce_scatter(x, ctx=None, mesh: Optional[Mesh] = None,
                   axis: str = "sp", op: str = "sum",
                   topo: Optional[str] = None, tp=None):
    """Reduce + scatter 1/R segments.

    Runtime path: `x` is this rank's contribution; returns THIS rank's
    flat segment of the reduction.  With `tp` (a live taskpool the
    caller runs), emits in-pool and returns the deferred segment buffer
    (ptc-shard; see all_reduce).  XLA path: `x` stacks contributions
    on dim 0; returns the FULL reduced array sharded along dim 0 of the
    result (device r holds segment r — materialized, so the caller sees
    every segment).  Neither: the whole local contribution."""
    if _runtime_live(ctx):
        if tp is not None:
            from ..comm.coll import reduce_scatter_into
            return reduce_scatter_into(ctx, tp, np.asarray(x), op=op,
                                       topo=topo)
        from ..comm.coll import reduce_scatter as _rs
        return _rs(ctx, np.asarray(x), op=op, topo=topo)
    if mesh is not None:
        if op != "sum":
            raise NotImplementedError(
                "XLA fallback reduce_scatter supports op='sum'")
        xs = _stacked(x, mesh, axis)
        n = mesh.shape[axis]
        flat = np.asarray(xs).reshape(n, -1)
        pad = (-flat.shape[1]) % n
        if pad:
            flat = np.concatenate(
                [flat, np.zeros((n, pad), flat.dtype)], axis=1)

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=P(axis, None),
                 out_specs=P(axis))
        def _f(s):
            return lax.psum_scatter(s[0], axis, scatter_dimension=0,
                                    tiled=True)

        return _f(flat)
    return np.ravel(np.asarray(x)).copy()


def all_gather(x, ctx=None, mesh: Optional[Mesh] = None,
               axis: str = "sp", topo: Optional[str] = None):
    """Concatenate per-rank contributions (rank order) on every rank.

    Runtime path: `x` is this rank's contribution; returns the flat
    R*size concatenation.  XLA path: `x` stacks contributions on dim 0;
    returns the replicated concatenation (flat).  Neither: the local
    contribution, flat."""
    if _runtime_live(ctx):
        from ..comm.coll import all_gather as _ag
        return _ag(ctx, np.asarray(x), topo=topo)
    if mesh is not None:
        xs = _stacked(x, mesh, axis)
        n = mesh.shape[axis]
        flat = np.asarray(xs).reshape(n, -1)

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=P(axis, None),
                 out_specs=P(None))
        def _f(s):
            return lax.all_gather(s, axis, axis=0, tiled=True)

        return _f(flat).reshape(-1)
    return np.ravel(np.asarray(x)).copy()


def broadcast(x, root: int = 0, ctx=None, mesh: Optional[Mesh] = None,
              axis: str = "sp", topo: Optional[str] = None):
    """Broadcast the root's contribution to every rank.

    Runtime path: every rank passes a same-shape `x`, the root's values
    win (returned on all ranks).  XLA path: `x` stacks per-device
    contributions on dim 0; returns contribution `root`, replicated.
    Neither: `x` itself (the caller IS the root)."""
    if _runtime_live(ctx):
        from ..comm.coll import broadcast as _bc
        return _bc(ctx, np.asarray(x), root=root, topo=topo)
    if mesh is not None:
        xs = _stacked(x, mesh, axis)
        n = mesh.shape[axis]
        flat = np.asarray(xs).reshape(n, -1)
        shape = xs.shape[1:]

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=P(axis, None),
                 out_specs=P(None))
        def _f(s):
            return lax.all_gather(s, axis, axis=0, tiled=True)[root]

        return _f(flat).reshape(shape)
    return np.asarray(x).copy()
