"""Pipeline parallelism: a GPipe-style microbatch pipeline over a `pp`
mesh axis.

The reference gets pipelining for free from dependency chains across
ranks (SURVEY.md §2.10 "Pipeline parallelism": examples/Ex02-Ex04, the
GEMM chain of tests/dsl/ptg/cuda/nvlink.jdf:126-130) with the comm thread
overlapping transfers.  The TPU-native equivalent is an explicit SPMD
schedule: each pipeline stage owns a contiguous slab of layers (its
"rank"), activations hop stage->stage+1 by `lax.ppermute` (ICI neighbor
traffic), and microbatches keep every stage busy after the fill phase —
n_microbatch + n_stages - 1 ticks total, the classic GPipe schedule.
"""
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable, stage_params, x_mb, mesh: Mesh,
          axis: str = "pp"):
    """Run a shape-preserving stage function as a GPipe pipeline.

    stage_fn(params_i, x) -> y        (same shape as x; one stage's layers)
    stage_params: pytree whose leaves have leading dim n_stages, sharded
                  over `axis` (stage i's slice lives on pp rank i).
    x_mb:         [n_microbatch, mb, ...] microbatched input (replicated
                  along `axis`; shard other dims as you like *outside*).
    Returns [n_microbatch, mb, ...] — the output of the last stage,
    replicated along `axis`.
    """
    n_stages = mesh.shape[axis]
    n_mb = x_mb.shape[0]
    # params sharded over pp on the leading (stage) dim; x replicated on pp
    p_spec = jax.tree.map(lambda _: P(axis), stage_params)
    rest = P(*([None] * x_mb.ndim))

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(p_spec, rest),
             out_specs=rest)
    def _pipe(params_loc, xs):
        # leading stage dim is 1 on each device — squeeze it away
        params_i = jax.tree.map(lambda a: a[0], params_loc)
        s = lax.axis_index(axis)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        mb_shape = xs.shape[1:]

        def tick(t, carry):
            act, outs = carry
            # stage 0 injects microbatch t during the fill+steady phase
            inj = lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_mb - 1), 0, keepdims=False)
            act = jnp.where((s == 0) & (t < n_mb), inj, act)
            y = stage_fn(params_i, act)
            # last stage banks its result for microbatch t-(n_stages-1)
            idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
            bank = lax.dynamic_update_index_in_dim(outs, y, idx, 0)
            take = (s == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(take, bank, outs)
            act_next = lax.ppermute(y, axis, perm)
            return act_next, outs

        act0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros_like(xs)
        _, outs = lax.fori_loop(0, n_mb + n_stages - 1, tick, (act0, outs0))
        # replicate the last stage's banked outputs to every pp rank
        keep = jnp.where(s == n_stages - 1, 1, 0).astype(outs.dtype)
        return lax.psum(outs * keep, axis)

    return _pipe(stage_params, x_mb)
