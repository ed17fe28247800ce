"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer family (models/transformer.py) and the
per-block compute of ring attention (parallel/ring_attention.py).  One
fused kernel computes softmax(QK^T * scale [+ causal mask]) V blockwise
with the online-softmax recurrence held in VMEM scratch — no [L, L]
score matrix ever materializes in HBM.

Kernel shape: grid (batch*heads, q_blocks, kv_blocks); the kv axis is
"arbitrary" (sequential) so the running max/sum/accumulator scratch
carries across kv steps; q/batch axes are parallel.  Blocks default to
128 (MXU-aligned); f32 accumulation (guide: preferred_element_type).

`flash_attention` is differentiable: forward runs the kernel, backward
falls back to the jnp reference VJP (recompute strategy) — exact same
math, so gradients match the oracle.
"""
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1.0e30


def for_backend(call, interpret: Optional[bool]):
    """`call(interpret)` with interpret mode chosen by the backend the
    program is LOWERED for when `interpret` is None: the Mosaic kernel
    for a TPU, the Pallas interpreter for any other backend.  Decided at
    lowering (lax.platform_dependent), never from jax.devices(): a
    program compiled for a described TPU from a CPU process still gets
    the real kernel."""
    if interpret is not None:
        return call(interpret)
    return jax.lax.platform_dependent(tpu=lambda: call(False),
                                      default=lambda: call(True))


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, block_q: int, block_k: int,
               nk: int):
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: the whole kv block is masked iff its first key index exceeds
    # the last query index of this q block — skip the matmuls entirely
    run = (i_k * block_k <= (i_q + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            qpos = i_q * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = i_k * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos > qpos, _NEG_BIG, s)
        m_prev = m_ref[:, :1]                        # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            # rows fully masked in this block contribute nothing even when
            # m_new == _NEG_BIG (exp(0) == 1 would poison them otherwise)
            p = jnp.where(s <= _NEG_BIG / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    @pl.when(i_k == nk - 1)
    def _final():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _flash_fwd(q, k, v, causal: bool, scale: float, block_q: int,
               block_k: int, interpret: Optional[bool]):
    bh, lq, d = q.shape
    lk = k.shape[1]
    nq, nk = _cdiv(lq, block_q), _cdiv(lk, block_k)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk)
    return for_backend(lambda interp: pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        interpret=interp,
    )(q, k, v), interpret)


def _reference(q, k, v, causal, scale):
    s = jnp.einsum("bld,bsd->bls", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(lk)[None, :] > jnp.arange(lq)[:, None]
        s = jnp.where(mask, -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bls,bsd->bld", p, v.astype(jnp.float32)).astype(
        q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    # recompute-backward through the mathematically identical reference
    _, vjp = jax.vjp(lambda q, k, v: _reference(q, k, v, causal, scale),
                     q, k, v)
    return vjp(g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Fused blockwise attention.  q,k,v: [B, L, H, D] -> [B, L, H, D].

    `interpret=None` lets the backend being lowered for decide
    (for_backend): Mosaic on a TPU, the Pallas interpreter elsewhere.  Falls
    back to the jnp reference when L is smaller than one block (the
    kernel would be all padding)."""
    b, l, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    if l < block_q or l < block_k:
        return _reference(
            jnp.reshape(jnp.transpose(q, (0, 2, 1, 3)), (b * h, l, d)),
            jnp.reshape(jnp.transpose(k, (0, 2, 1, 3)), (b * h, l, d)),
            jnp.reshape(jnp.transpose(v, (0, 2, 1, 3)), (b * h, l, d)),
            causal, scale).reshape(b, h, l, d).transpose(0, 2, 1, 3)
    if l % block_q or l % block_k:
        raise ValueError(f"seq len {l} must divide by blocks "
                         f"({block_q}, {block_k})")

    def fold(x):
        return jnp.reshape(jnp.transpose(x, (0, 2, 1, 3)), (b * h, l, d))

    out = _flash(fold(q), fold(k), fold(v), causal, scale,
                 block_q, block_k, interpret)
    return jnp.transpose(out.reshape(b, h, l, d), (0, 2, 1, 3))


# ------------------------------------------------------------ PTG builder
def build_flash_attention(ctx, Qc, Kc, Vc, Oc, causal: bool = False,
                          scale: Optional[float] = None, dev=None,
                          names=("FAQ", "FAK", "FAV", "FAO")):
    """Blockwise attention as a PTG taskpool: FATT(q) attends row block
    q of `Qc` over the full `Kc`/`Vc` into `Oc` — the runtime-task form
    of this op (one task per query block, fully parallel; block-level
    causality masks by absolute row), so attention composes with other
    tile DAGs instead of leaving the runtime for a whole-array XLA
    call.  The sequence-sharded, KV-rotating variant is
    algos/ring_attention.py.

    Qc/Oc: (B*L, d) collections tiled (T, d); Kc/Vc: one (L, d) tile
    each.  Registers the collections under `names`.  With `dev`, the
    chore runs the fused Pallas kernel (flash_attention); the CPU body
    is the numpy reference."""
    import numpy as np

    import parsec_tpu as pt

    assert Qc.mt == Oc.mt and Qc.mb == Oc.mb and Qc.nb == Oc.nb
    qn, kn, vn, on = names
    Qc.register(ctx, qn)
    Kc.register(ctx, kn)
    Vc.register(ctx, vn)
    Oc.register(ctx, on)
    tp = pt.Taskpool(ctx, globals={"NQ": Qc.mt - 1})
    q = pt.L("q")
    T, d = Qc.mb, Qc.nb
    L = Kc.mb
    sc = (d ** -0.5) if scale is None else scale
    qshp, kshp = (T, d), (L, d)
    dt = Qc.dtype

    tc = tp.task_class("FATT")
    tc.param("q", 0, pt.G("NQ"))
    tc.affinity(qn, q, 0)
    tc.flow("Q", "READ", pt.In(pt.Mem(qn, q, 0)))
    tc.flow("K", "READ", pt.In(pt.Mem(kn, 0, 0)))
    tc.flow("V", "READ", pt.In(pt.Mem(vn, 0, 0)))
    tc.flow("O", "RW", pt.In(pt.Mem(on, q, 0)),
            pt.Out(pt.Mem(on, q, 0)))

    if dev is not None:
        def k_fatt(qb, kb, vb, _q=None):
            if qb.shape[0] == kb.shape[0]:
                # [T, d] block through the fused kernel as [1, T, 1, d]
                o = flash_attention(qb[None, :, None, :],
                                    kb[None, :, None, :],
                                    vb[None, :, None, :],
                                    causal=False, scale=sc)
                return o[0, :, 0, :]
            # T != L (a multi-block Q attending the full K/V tile): the
            # fused kernel's internal reshape assumes square self-
            # attention, so the blockwise softmax runs directly — the
            # same op order as the CPU reference body
            import jax.numpy as jnp
            s = (qb @ kb.T) * sc
            s = s - s.max(axis=-1, keepdims=True)
            p = jnp.exp(s)
            p = p / p.sum(axis=-1, keepdims=True)
            return (p @ vb).astype(qb.dtype)

        if causal:
            raise ValueError(
                "build_flash_attention: causal device chores need the "
                "per-block row offset; use the CPU bodies (dev=None) "
                "or algos/ring_attention for causal DAG attention")
        dev.attach(tc, tp, kernel=k_fatt, reads=["Q", "K", "V"],
                   writes=["O"],
                   shapes={"Q": qshp, "K": kshp, "V": kshp, "O": qshp},
                   dtype=dt)

    def body(t):
        qb = t.data("Q", dt, qshp).astype(np.float32)
        kb = t.data("K", dt, kshp).astype(np.float32)
        vb = t.data("V", dt, kshp).astype(np.float32)
        o = t.data("O", dt, qshp)
        s = (qb @ kb.T) * sc
        if causal:
            off = t.local("q") * T
            rows = off + np.arange(T)[:, None]
            s = np.where(rows >= np.arange(L)[None, :], s, -np.inf)
        s = s - s.max(axis=-1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=-1, keepdims=True)
        o[...] = (p @ vb).astype(dt)

    tc.body(body, pure=True)  # pure tile chore: fusion-eligible
    return tp
