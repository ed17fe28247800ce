"""Pallas fused RMSNorm: one VMEM pass per row block computes the
mean-square, normalizes, and applies the scale — the elementwise+
reduction chain XLA would otherwise split across HBM round trips on the
boundary of fusion clusters.  Second hand-written device kernel next to
ops/flash_attention.py (reference contrast: hand-written cuBLAS/cuDNN
kernels dyld'd per chore, device_cuda_module.c:175).

Forward is the fused Pallas kernel; backward is plain jnp through a
custom VJP (the backward chain is matmul-shaped and XLA already fuses it
well — fusing the forward is where the win is)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import for_backend


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...]
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                  keepdims=True)
    r = jax.lax.rsqrt(ms + eps)
    o_ref[...] = (x.astype(jnp.float32) * r).astype(x.dtype) * w_ref[...]


def _rms_fwd_pallas(x2d, w, eps, block_rows, interpret):
    n, d = x2d.shape
    grid = (n // block_rows,)
    return for_backend(lambda interp: pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=interp,
    )(x2d, w), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rms(x2d, w, eps, block_rows, interpret):
    return _rms_fwd_pallas(x2d, w, eps, block_rows, interpret)


def _rms_vjp_fwd(x2d, w, eps, block_rows, interpret):
    return _rms_fwd_pallas(x2d, w, eps, block_rows, interpret), (x2d, w)


def _rms_vjp_bwd(eps, block_rows, interpret, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(ms + eps)
    xhat = xf * r
    gw = gf * wf
    d = x.shape[-1]
    # dx = r*gw - x * (sum(gw*x)/d) * r^3   (d/dx of x*rsqrt(mean x^2))
    dx = r * gw - xf * (jnp.sum(gw * xf, axis=-1, keepdims=True) / d) \
        * (r ** 3)
    dw = jnp.sum(gf * xhat, axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rms.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


def rms_norm(x, w, eps: float = 1e-6, block_rows: int = 128,
             interpret: Optional[bool] = None):
    """y = x / sqrt(mean(x^2, -1) + eps) * w over the last dim.

    Any leading shape; `interpret=None` lets the backend being lowered
    for decide (for_backend).  Falls back to plain jnp when the row count
    doesn't fill one block, or when the last dim violates the TPU lane
    tiling (d % 128) — Mosaic would reject the kernel on hardware even
    though interpret mode happily runs it."""
    d = x.shape[-1]
    lead = x.shape[:-1]
    n = 1
    for s in lead:
        n *= s
    if n % block_rows or d % 128:
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                      keepdims=True)
        return (x.astype(jnp.float32)
                * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w
    out = _rms(x.reshape(n, d), w, eps, block_rows, interpret)
    return out.reshape(*lead, d)


# ------------------------------------------------------------ PTG builder
def build_rms_norm(ctx, Xc, Wc, Oc, eps: float = 1e-6, dev=None,
                   names=("RNX", "RNW", "RNO")):
    """Tile-granular RMSNorm as a PTG taskpool: NORM(r) normalizes row
    tile r of `Xc` against the shared scale tile `Wc` into `Oc` —
    the runtime-task form of this op (one task per row block, fully
    parallel), so norm layers compose with other tile DAGs instead of
    leaving the runtime for a whole-array XLA call.

    Xc/Oc: (R*T, d) collections tiled (T, d); Wc: one (1, d) tile.
    Registers the collections under `names`.  With `dev`, the chore is
    the fused Pallas kernel (rms_norm); the CPU body is the numpy
    reference."""
    import numpy as np

    import parsec_tpu as pt

    assert Xc.mt == Oc.mt and Xc.mb == Oc.mb and Xc.nb == Oc.nb
    xn, wn, on = names
    Xc.register(ctx, xn)
    Wc.register(ctx, wn)
    Oc.register(ctx, on)
    tp = pt.Taskpool(ctx, globals={"R": Xc.mt - 1})
    r = pt.L("r")
    shp = (Xc.mb, Xc.nb)
    wshp = (Wc.mb, Wc.nb)
    dt = Xc.dtype

    tc = tp.task_class("NORM")
    tc.param("r", 0, pt.G("R"))
    tc.affinity(xn, r, 0)
    tc.flow("X", "READ", pt.In(pt.Mem(xn, r, 0)))
    tc.flow("W", "READ", pt.In(pt.Mem(wn, 0, 0)))
    tc.flow("O", "RW", pt.In(pt.Mem(on, r, 0)),
            pt.Out(pt.Mem(on, r, 0)))

    if dev is not None:
        def k_norm(x, w):
            return rms_norm(x, w[0], eps)

        dev.attach(tc, tp, kernel=k_norm, reads=["X", "W"],
                   writes=["O"],
                   shapes={"X": shp, "W": wshp, "O": shp}, dtype=dt)

    def body(t):
        x = t.data("X", dt, shp).astype(np.float32)
        w = t.data("W", dt, wshp)[0].astype(np.float32)
        o = t.data("O", dt, shp)
        ms = np.mean(np.square(x), axis=-1, keepdims=True)
        o[...] = (x / np.sqrt(ms + eps) * w).astype(dt)

    tc.body(body, pure=True)  # pure tile chore: fusion-eligible
    return tp
