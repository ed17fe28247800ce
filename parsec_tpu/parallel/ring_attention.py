"""Ring attention: exact long-context attention over a sequence-sharded
ring of devices.

The communication shape is the reference's chain-pipeline broadcast
topology (parsec/remote_dep.c:39-47) mapped onto the ICI torus: each step
every device computes blockwise attention of its local Q against the
resident K/V block while `lax.ppermute` rotates the K/V blocks one
neighbor around the ring — comm/compute overlap exactly as the reference's
comm thread overlaps MPI with task execution (SURVEY.md §3.3).  Softmax is
accumulated online (running max / running sum), so the result is exact,
not approximate.

All shapes static, loop is `lax.fori_loop` — XLA-friendly (no Python
control flow inside jit), MXU-friendly (block matmuls, f32 accumulate).
"""
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_BIG = -1.0e30


def blockwise_attention_reference(q, k, v, causal: bool = False,
                                  scale: Optional[float] = None):
    """Plain full attention on one device — the test oracle.

    q,k,v: [B, L, H, D] -> [B, L, H, D]."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("blhd,bshd->bhls", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(lk)[None, :] > jnp.arange(lq)[:, None]
        s = jnp.where(mask, -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhls,bshd->blhd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def _ring_block_step(q, k_blk, v_blk, o, m, l, q_off, k_off, causal, scale):
    """One online-softmax accumulation of q against a K/V block.

    q: [B,Lq,H,D]; k_blk,v_blk: [B,Lk,H,D]; o: [B,Lq,H,D] f32;
    m,l: [B,H,Lq] f32.  q_off/k_off are the blocks' global sequence
    offsets (traced scalars) used for causal masking."""
    s = jnp.einsum("blhd,bshd->bhls", q.astype(jnp.float32),
                   k_blk.astype(jnp.float32)) * scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        qpos = q_off + jnp.arange(lq)
        kpos = k_off + jnp.arange(lk)
        s = jnp.where(kpos[None, :] > qpos[:, None], -jnp.inf, s)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))            # [B,H,Lq]
    p = jnp.exp(s - m_new[..., None])                      # masked -> 0
    corr = jnp.exp(m - m_new)                              # [B,H,Lq]
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhls,bshd->blhd", p, v_blk.astype(jnp.float32))
    o_new = o * jnp.transpose(corr, (0, 2, 1))[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, scale: Optional[float] = None,
                   spec: Optional[P] = None):
    """Exact attention with q,k,v sequence-sharded on mesh axis `axis`.

    q,k,v: [B, L, H, D] with L sharded over `axis` (n_sp shards).
    `spec` overrides the q/k/v partition spec when batch/heads are also
    sharded (e.g. P('dp', 'sp', 'tp', None) in the transformer); the ring
    still only rotates along `axis`.  Returns [B, L, H, D], same sharding.
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    n = mesh.shape[axis]
    pspec = spec if spec is not None else P(None, axis, None, None)

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(pspec, pspec, pspec),
             out_specs=pspec)
    def _ring(q_loc, k_loc, v_loc):
        b, lc, h, _ = q_loc.shape
        r = lax.axis_index(axis)
        q_off = r * lc
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(t, carry):
            o, m, l, k_cur, v_cur = carry
            src = (r - t) % n                 # origin block of resident K/V
            o, m, l = _ring_block_step(q_loc, k_cur, v_cur, o, m, l,
                                       q_off, src * lc, causal, scale)
            # Rotate K/V to the ring neighbor (overlaps with the next
            # step's matmuls once XLA schedules the collective-permute).
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return o, m, l, k_nxt, v_nxt

        o0 = jnp.zeros(q_loc.shape, jnp.float32)
        m0 = jnp.full((b, h, lc), _NEG_BIG, jnp.float32)
        l0 = jnp.zeros((b, h, lc), jnp.float32)
        # n-1 compute+rotate steps, then the last block's accumulation
        # outside the loop — no trailing ppermute whose result is dropped.
        o, m, l, k_fin, v_fin = lax.fori_loop(
            0, n - 1, body, (o0, m0, l0, k_loc, v_loc))
        o, m, l = _ring_block_step(q_loc, k_fin, v_fin, o, m, l,
                                   q_off, ((r - (n - 1)) % n) * lc,
                                   causal, scale)
        l_t = jnp.transpose(l, (0, 2, 1))[..., None]       # [B,Lq,H,1]
        return (o / jnp.maximum(l_t, 1e-30)).astype(q_loc.dtype)

    return _ring(q, k, v)
