"""Ulysses (all-to-all) sequence parallelism.

Instead of rotating K/V around a ring, reshard with one all-to-all so each
device holds the FULL sequence for a subset of heads, runs dense local
attention, and all-to-all's back to sequence sharding.  This is the
reference's generic redistribute
(parsec/data_dist/matrix/redistribute/redistribute.jdf — collection ->
collection resharding, SURVEY.md §2.3) specialized to the uniform
head<->sequence exchange, fused into a single XLA all-to-all on ICI.

Trade-off vs ring attention: 2 all-to-alls of Q,K,V,O total traffic but
one big MXU-saturating attention per device; requires n_heads % n_sp == 0.
"""
from functools import partial
from typing import Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import blockwise_attention_reference


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = False, scale: Optional[float] = None,
                      local_attn=None):
    """Exact attention with q,k,v sequence-sharded on mesh axis `axis`.

    q,k,v: [B, L, H, D], L sharded over `axis`; H % mesh.shape[axis] == 0.
    `local_attn(q, k, v, causal=..., scale=...)` overrides the per-device
    attention over the gathered sequence (e.g. ops.flash_attention — the
    Pallas kernel — on TPU).  Returns [B, L, H, D], same sharding."""
    n = mesh.shape[axis]
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"ulysses needs n_heads ({h}) divisible by "
                         f"mesh axis '{axis}' size ({n})")
    attn = local_attn if local_attn is not None else \
        blockwise_attention_reference
    pspec = P(None, axis, None, None)

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(pspec, pspec, pspec),
             out_specs=pspec)
    def _uly(q_loc, k_loc, v_loc):
        # [B, L/n, H, D] -> [B, L, H/n, D]: gather sequence, split heads.
        def fwd(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        qf, kf, vf = fwd(q_loc), fwd(k_loc), fwd(v_loc)
        of = attn(qf, kf, vf, causal=causal, scale=scale)
        # [B, L, H/n, D] -> [B, L/n, H, D]: back to sequence sharding.
        return lax.all_to_all(of, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    return _uly(q, k, v)
