"""Tiled Cholesky factorization (lower) as a PTG taskpool — the DPLASMA
dpotrf_L dataflow (the BASELINE north-star workload), built from four task
classes over a 2D block-cyclic matrix:

  POTRF(k)    : diagonal tile factor        A[k,k] = chol(A[k,k])
  TRSM(m,k)   : panel solve                 A[m,k] = A[m,k] inv(L[k,k])^T
  SYRK(k,m)   : diagonal trailing update    A[m,m] -= A[m,k] A[m,k]^T
  GEMM(m,n,k) : off-diag trailing update    A[m,n] -= A[m,k] A[n,k]^T

Kernels run as cached XLA executables on the TPU device, with numpy CPU
fallback chores.  Priorities favor the critical path (deeper k first),
matching the reference's priority-expression practice in dense LA JDFs.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import parsec_tpu as pt
from ..data.collections import TwoDimBlockCyclic
from ..device.tpu import TpuDevice

from ._util import as_device_list

# Precision of every matmul the tile and panel kernels issue: fp32.  XLA's
# default on a TPU is one bf16 pass for fp32 operands, and a v5e run at it
# missed the sqrt(N)*eps32 componentwise check ~180x (3.9e-3; PR 21).
MATMUL_PRECISION = "highest"


# ---------------------------------------------------------------- kernels
# module-level so their identity is stable: jax.jit keeps ONE compiled
# executable per (kernel, tile shape, dtype) across taskpools/processes
def k_potrf(t):
    import jax.numpy as jnp
    return jnp.linalg.cholesky(t)


def k_trsm(l, c):
    import jax
    return jax.scipy.linalg.solve_triangular(l, c.T, lower=True).T


def k_potrf_inv(t):
    """POTRF that also emits inv(L): ONE small triangular solve per panel
    turns every TRSM in the panel's wave into a plain batched GEMM — the
    MXU runs matmuls an order of magnitude faster than XLA's blocked
    triangular solve runs on a whole wave of tiles (tools/
    probe_la_kernels.py quantifies the gap per chip).  Standard
    inversion-based TRSM practice from GPU dense LA, TPU-shaped."""
    import jax
    import jax.numpy as jnp
    l = jnp.linalg.cholesky(t)
    linv = jax.scipy.linalg.solve_triangular(
        l, jnp.eye(t.shape[0], dtype=t.dtype), lower=True)
    return l, linv


def k_trsm_mm(linv, c):
    """TRSM as GEMM: X L^T = C  ->  X = C inv(L)^T."""
    import jax
    return jax.lax.dot_general(c, linv, (((1,), (1,)), ((), ())),
                               preferred_element_type=c.dtype,
                               precision=MATMUL_PRECISION)


def k_syrk(a, t):
    import jax
    return t - jax.lax.dot_general(a, a, (((1,), (1,)), ((), ())),
                                   preferred_element_type=t.dtype,
                                   precision=MATMUL_PRECISION)


def k_gemm(a, b, c):
    import jax
    return c - jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=c.dtype,
                                   precision=MATMUL_PRECISION)


def build_potrf(ctx: pt.Context, A: TwoDimBlockCyclic,
                dev: Optional[TpuDevice] = None,
                name: str = "A",
                trsm_via_inverse: bool = True) -> pt.Taskpool:
    """Build the Cholesky taskpool for the square tiled SPD matrix `A`
    (registered with ctx under `name`).  A.mt == A.nt required.

    trsm_via_inverse (default): POTRF(k) additionally emits inv(L[k,k])
    through a W temp flow and TRSM becomes a batched GEMM against it —
    one extra NB-size triangular solve per PANEL instead of one per
    TILE, and the whole TRSM wave rides the MXU.  Set False for the
    textbook solve_triangular dataflow."""
    nt = A.mt
    assert A.mt == A.nt and A.mb == A.nb
    nb = A.mb
    tp = pt.Taskpool(ctx, globals={"NT": nt - 1})
    k, m, n = pt.L("k"), pt.L("m"), pt.L("n")
    NT = pt.G("NT")
    shp = (nb, nb)
    dt = A.dtype
    if trsm_via_inverse:
        li_arena = f"potrf_li_{nb}_{np.dtype(dt).str}"
        if li_arena not in ctx.arenas:  # re-builds must not leak an id
            ctx.register_arena(li_arena, nb * nb * np.dtype(dt).itemsize)

    # ------------------------------------------------------------- POTRF(k)
    po = tp.task_class("POTRF")
    po.param("k", 0, NT)
    po.affinity(name, k, k)
    po.priority((NT - k) * 1000)
    if trsm_via_inverse:
        po.flow("T", "RW",
                pt.In(pt.Mem(name, k, k), guard=(k == 0)),
                pt.In(pt.Ref("SYRK", k - 1, k, flow="T")),
                pt.Out(pt.Mem(name, k, k)))
        # the panel inverse: consumed by every TRSM in this panel's wave
        po.flow("I", "W",
                pt.Out(pt.Ref("TRSM", k, pt.Range(k + 1, NT), flow="L"),
                       guard=(k < NT)),
                arena=li_arena)
    else:
        po.flow("T", "RW",
                pt.In(pt.Mem(name, k, k), guard=(k == 0)),
                pt.In(pt.Ref("SYRK", k - 1, k, flow="T")),
                pt.Out(pt.Ref("TRSM", k, pt.Range(k + 1, NT), flow="L"),
                       guard=(k < NT)),
                pt.Out(pt.Mem(name, k, k)))

    # ----------------------------------------------------------- TRSM(m, k)
    tr = tp.task_class("TRSM")
    tr.param("k", 0, NT)
    tr.param("m", k + 1, NT)
    tr.affinity(name, m, k)
    tr.priority((NT - k) * 1000 - m)
    tr.flow("L", "READ",
            pt.In(pt.Ref("POTRF", k, flow="I" if trsm_via_inverse
                         else "T")))
    # NB: GEMM's declared param order is (k, m, n) — Refs must match it
    tr.flow("C", "RW",
            pt.In(pt.Mem(name, m, k), guard=(k == 0)),
            pt.In(pt.Ref("GEMM", k - 1, m, k, flow="C")),
            # SYRK(k, m) updates diagonal (m, m) with this panel
            pt.Out(pt.Ref("SYRK", k, m, flow="A")),
            # GEMM row m: A[m, n] for k < n < m uses this as the A operand
            pt.Out(pt.Ref("GEMM", k, m, pt.Range(k + 1, m - 1), flow="A"),
                   guard=(m > k + 1)),
            # GEMM column m: A[mm, m] for m < mm <= NT uses it as B operand
            pt.Out(pt.Ref("GEMM", k, pt.Range(m + 1, NT), m, flow="B"),
                   guard=(m < NT)),
            pt.Out(pt.Mem(name, m, k)))

    # ----------------------------------------------------------- SYRK(k, m)
    sy = tp.task_class("SYRK")
    sy.param("k", 0, NT)
    sy.param("m", k + 1, NT)
    sy.affinity(name, m, m)
    sy.priority((NT - k) * 1000 - m)
    sy.flow("A", "READ", pt.In(pt.Ref("TRSM", k, m, flow="C")))
    sy.flow("T", "RW",
            pt.In(pt.Mem(name, m, m), guard=(k == 0)),
            pt.In(pt.Ref("SYRK", k - 1, m, flow="T")),
            pt.Out(pt.Ref("POTRF", m, flow="T"), guard=(m == k + 1)),
            pt.Out(pt.Ref("SYRK", k + 1, m, flow="T"), guard=(m > k + 1)))

    # -------------------------------------------------------- GEMM(m, n, k)
    ge = tp.task_class("GEMM")
    ge.param("k", 0, NT)
    ge.param("m", k + 2, NT)
    ge.param("n", k + 1, m - 1)
    ge.affinity(name, m, n)
    ge.priority((NT - k) * 1000 - m - n)
    ge.flow("A", "READ", pt.In(pt.Ref("TRSM", k, m, flow="C")))
    ge.flow("B", "READ", pt.In(pt.Ref("TRSM", k, n, flow="C")))
    ge.flow("C", "RW",
            pt.In(pt.Mem(name, m, n), guard=(k == 0)),
            pt.In(pt.Ref("GEMM", k - 1, m, n, flow="C")),
            pt.Out(pt.Ref("TRSM", n, m, flow="C"), guard=(n == k + 1)),
            pt.Out(pt.Ref("GEMM", k + 1, m, n, flow="C"), guard=(n > k + 1)))

    # --------------------------------------------------------------- chores
    # one or several devices: each attach adds a device chore; the native
    # best-device routing load-balances task instances across the queues
    # (reference: parsec_get_best_device, device.c:79-160), and sibling
    # mirrors stage D2D over the fabric
    for d in as_device_list(dev):
        if trsm_via_inverse:
            d.attach(po, tp, kernel=k_potrf_inv, reads=["T"],
                     writes=["T", "I"], shapes={"T": shp, "I": shp},
                     dtype=dt)
            d.attach(tr, tp, kernel=k_trsm_mm, reads=["L", "C"],
                     writes=["C"], shapes={"L": shp, "C": shp}, dtype=dt)
        else:
            d.attach(po, tp, kernel=k_potrf, reads=["T"], writes=["T"],
                     shapes={"T": shp}, dtype=dt)
            d.attach(tr, tp, kernel=k_trsm, reads=["L", "C"],
                     writes=["C"], shapes={"L": shp, "C": shp}, dtype=dt)
        d.attach(sy, tp, kernel=k_syrk, reads=["A", "T"], writes=["T"],
                 shapes={"A": shp, "T": shp}, dtype=dt)
        d.attach(ge, tp, kernel=k_gemm, reads=["A", "B", "C"], writes=["C"],
                 shapes={"A": shp, "B": shp, "C": shp}, dtype=dt)

    def b_potrf(t):
        a = t.data("T", dt, shp)
        a[...] = np.linalg.cholesky(a)
        if trsm_via_inverse:
            li = t.data("I", dt, shp)
            li[...] = np.linalg.solve(a, np.eye(nb, dtype=dt))

    def b_trsm(t):
        l = t.data("L", dt, shp)  # inv(L) when trsm_via_inverse
        c = t.data("C", dt, shp)
        if trsm_via_inverse:
            c[...] = c @ l.T
        else:
            # X L^T = C -> X = (L^-1 C^T)^T ; use lapack-free solve
            c[...] = np.linalg.solve(l, c.T).T

    def b_syrk(t):
        a = t.data("A", dt, shp)
        x = t.data("T", dt, shp)
        x -= a @ a.T

    def b_gemm(t):
        a = t.data("A", dt, shp)
        b = t.data("B", dt, shp)
        c = t.data("C", dt, shp)
        c -= a @ b.T

    # pure tile chores (read/write only their declared flows): the
    # declaration makes homogeneous waves fusion-eligible for the
    # wave-fusability certificate (analysis/plan.py certify())
    po.body(b_potrf, pure=True)
    tr.body(b_trsm, pure=True)
    sy.body(b_syrk, pure=True)
    ge.body(b_gemm, pure=True)
    return tp


# ------------------------------------------------------ panel variant
# Right-looking blocked Cholesky at PANEL granularity: tasks operate on
# full-height N x nb column panels instead of nb x nb tiles.  Same math
# as the tiled dataflow (DPLASMA dpotrf_L), coarser tasks: each trailing
# update U(k, j) is ONE (N x nb) @ (nb x nb) MXU matmul, and a wave of
# them is one vmapped call — the TPU-shaped answer to the tile DAG's
# launch-overhead wall on a single fat chip.  (The panel-granular,
# few-big-matmuls shape follows the published TPU dense-LA recipe —
# "Large Scale Distributed Linear Algebra With Tensor Processing
# Units", arXiv:2112.09017 — recast as runtime task dataflow.)  The
# tiled build_potrf remains the distributed (PxQ block-cyclic) form.
#
#   F(k)   : factor panel k   diag = chol(P[kb:kb+nb]); P = P inv(L)^T
#            (rows above kb zeroed, diag block set to L exactly)
#   U(k,j) : panel j trailing update   P_j -= P_k P_k[jb:jb+nb]^T
#
# Panel row offsets ride a tiny int32 index collection (kernels receive
# only flow arrays; the offset is data, not a compile-time constant, so
# ONE executable serves every k).


def k_panel_factor(p, ks):
    import jax
    import jax.numpy as jnp
    nb = p.shape[1]
    off = ks[0] * nb
    diag = jax.lax.dynamic_slice(p, (off, 0), (nb, nb))
    l = jnp.linalg.cholesky(diag)
    linv = jax.scipy.linalg.solve_triangular(
        l, jnp.eye(nb, dtype=p.dtype), lower=True)
    x = jax.lax.dot_general(p, linv, (((1,), (1,)), ((), ())),
                            preferred_element_type=p.dtype,
                            precision=MATMUL_PRECISION)
    rows = jnp.arange(p.shape[0], dtype=ks.dtype)[:, None]
    x = jnp.where(rows >= off, x, jnp.zeros((), p.dtype))
    return jax.lax.dynamic_update_slice(x, l, (off, 0))


def k_panel_update(pk, js, pj):
    import jax
    nb = pk.shape[1]
    off = js[0] * nb
    bj = jax.lax.dynamic_slice(pk, (off, 0), (nb, nb))
    return pj - jax.lax.dot_general(pk, bj, (((1,), (1,)), ((), ())),
                                    preferred_element_type=pj.dtype,
                                    precision=MATMUL_PRECISION)


def _register_pidx(ctx: pt.Context, A: TwoDimBlockCyclic, name: str):
    """Register (once) the int32 panel-index collection `name + "_pidx"`
    following A's panel-cyclic map, so every Mem(pidx, j) read is
    co-located with the task that issues it."""
    from ..data.collections import VectorCyclic
    pidx_name = name + "_pidx"
    # guard on OUR registry, not ctx.collections: a user collection that
    # happens to be named <name>_pidx must not satisfy the early return
    # (it has no _pidx_colls record and the wrong contents)
    if pidx_name in getattr(ctx, "_pidx_colls", {}):
        return pidx_name, ctx._pidx_colls[pidx_name]
    if pidx_name in ctx.collections:
        raise ValueError(
            f"collection name {pidx_name!r} is reserved for the panel "
            f"index of {name!r} but is already registered")
    pidx = VectorCyclic(A.nt, 1, nodes=A.nodes, myrank=A.myrank,
                        dtype=np.int32)
    for j in range(A.nt):
        pidx.seg(j)[0] = j
    pidx.register(ctx, pidx_name)
    if not hasattr(ctx, "_pidx_colls"):
        ctx._pidx_colls = {}
    ctx._pidx_colls[pidx_name] = pidx
    return pidx_name, pidx


def _build_panel_factorization(ctx: pt.Context, A: TwoDimBlockCyclic,
                               dev, name: str,
                               k_factor, k_update,
                               b_factor, b_update,
                               update_uses: str = "j") -> pt.Taskpool:
    """Shared panel-factorization DAG (right-looking, full-height
    panels): F(k) factors panel k, U(k, j) applies its rank-nb update to
    panel j, a U wave batches into one vmapped MXU call.  The algorithm
    lives in the kernel/body pair: Cholesky (build_potrf_panels) and
    no-pivot LU (build_getrf_panels) share this graph.

      F(k)   : P RW (chain from U(k-1,k)), KS index READ
      U(k,j) : PK READ (broadcast from F(k)), an index flow, PJ RW chain

    update_uses selects which panel index U's kernel needs:
      "j" — the TARGET panel's index, read co-located from the pidx
            collection (Cholesky slices the source panel at row block j)
      "k" — the SOURCE panel's index; pidx[k] is NOT co-located with
            U(k, j) on rank j, so F(k) emits it as a tiny KI arena flow
            that broadcasts WITH the panel (distributed-correct; LU
            solves at row block k).  k_factor then returns (panel, ki).

    Host bodies are built by b_factor/b_update factories given
    (nt, nb, pshp, dt)."""
    assert A.mt == 1 and A.M == A.N and A.M == A.mb, \
        "panel collection: mb == M (one block row of panels)"
    assert A.P == 1, "panels distribute 1-D: P must be 1 (Q = nodes)"
    nt = A.nt
    nb = A.nb
    NN = A.M
    dt = A.dtype
    pidx_name, pidx = _register_pidx(ctx, A, name)
    tp = pt.Taskpool(ctx, globals={"NT": nt - 1})
    k, j = pt.L("k"), pt.L("j")
    NT = pt.G("NT")

    # ------------------------------------------------------------- F(k)
    fa = tp.task_class("PF")
    fa.param("k", 0, NT)
    fa.affinity(name, 0, k)
    fa.priority((NT - k) * 1000 + 500)
    fa.flow("P", "RW",
            pt.In(pt.Mem(name, 0, k), guard=(k == 0)),
            pt.In(pt.Ref("PU", k - 1, k, flow="PJ")),
            pt.Out(pt.Ref("PU", k, pt.Range(k + 1, NT), flow="PK"),
                   guard=(k < NT)),
            pt.Out(pt.Mem(name, 0, k)))
    fa.flow("KS", "READ", pt.In(pt.Mem(pidx_name, k)))
    if update_uses == "k":
        ki_arena = f"panel_ki_{name}"
        if ki_arena not in ctx.arenas:  # re-builds must not leak an id
            ctx.register_arena(ki_arena, 4)
        fa.flow("KI", "W",
                pt.Out(pt.Ref("PU", k, pt.Range(k + 1, NT), flow="KI"),
                       guard=(k < NT)),
                arena=ki_arena)

    # ----------------------------------------------------------- U(k, j)
    up = tp.task_class("PU")
    up.param("k", 0, NT)
    up.param("j", k + 1, NT)
    up.affinity(name, 0, j)
    up.priority((NT - k) * 1000 - j)
    up.flow("PK", "READ", pt.In(pt.Ref("PF", k, flow="P")))
    if update_uses == "k":
        up.flow("KI", "READ", pt.In(pt.Ref("PF", k, flow="KI")))
    else:
        up.flow("JS", "READ", pt.In(pt.Mem(pidx_name, j)))
    up.flow("PJ", "RW",
            pt.In(pt.Mem(name, 0, j), guard=(k == 0)),
            pt.In(pt.Ref("PU", k - 1, j, flow="PJ")),
            pt.Out(pt.Ref("PF", j, flow="P"), guard=(j == k + 1)),
            pt.Out(pt.Ref("PU", k + 1, j, flow="PJ"), guard=(j > k + 1)))

    # --------------------------------------------------------------- chores
    pshp = (NN, nb)
    devs = as_device_list(dev)
    # pre-stage this rank's index segments as ONE stacked device array
    # per device: every wave's KS/JS gather then rides the fused
    # (stack, idx) path instead of an eager per-wave stack of h2d'd
    # scalars
    local = [k2 for k2 in range(nt) if pidx.rank_of(k2) == pidx.myrank]
    seg_host = np.asarray(local, dtype=np.int32).reshape(-1, 1)
    for d in devs:
        if local:
            from ..device.bench_utils import install_device_segments
            install_device_segments(
                d, pidx, d._jax.device_put(seg_host, d.device))
        idxf = "KI" if update_uses == "k" else "JS"
        d.attach(fa, tp, kernel=k_factor, reads=["P", "KS"],
                 writes=["P", "KI"] if update_uses == "k" else ["P"],
                 shapes={"P": pshp, "KS": (1,), "KI": (1,)},
                 dtypes={"P": np.dtype(dt), "KS": np.dtype(np.int32),
                         "KI": np.dtype(np.int32)})
        d.attach(up, tp, kernel=k_update, reads=["PK", idxf, "PJ"],
                 writes=["PJ"],
                 shapes={"PK": pshp, idxf: (1,), "PJ": pshp},
                 dtypes={"PK": np.dtype(dt), idxf: np.dtype(np.int32),
                         "PJ": np.dtype(dt)})
        # speculative epilogue (dispatch-economics lever): the U(k, k+1)
        # lane's output IS F(k+1)'s input — factor it inside the same
        # wave program, so the factor chain costs ONE device call per k
        # step instead of two.  F(k+1) then completes from the parked
        # result, version-checked.  Works for both variants: potrf's
        # factor returns the panel; getrf's returns (panel, KI), which
        # matches its two write flows (arity is validated at the hit).
        d.attach_epilogue(
            up, fa, tp, src_flow="PJ", dst_in_flow="P",
            pick=lambda v: ((v.local("j"),)
                            if v.local("j") == v.local("k") + 1
                            else None),
            dst_params=lambda v: (v.local("k"),),
            kernel=k_factor,
            ops=lambda key: [np.asarray([key[0]], dtype=np.int32)],
            # KS is the pivot-index flow: constant per k and folded into
            # ops (single-varying-input contract, see attach_epilogue)
            const_flows=("KS",))

    fa.body(b_factor(nt, nb, pshp, dt))
    up.body(b_update(nt, nb, pshp, dt))
    return tp


def _potrf_b_factor(nt, nb, pshp, dt):
    def b_factor(t):
        p = t.data("P", dt, pshp)
        kk = int(t.data("KS", np.int32, (1,))[0])
        off = kk * nb
        diag = p[off:off + nb]
        l = np.linalg.cholesky(diag)
        linv = np.linalg.solve(l, np.eye(nb, dtype=dt))
        x = p @ linv.T
        x[:off] = 0
        x[off:off + nb] = l
        p[...] = x
    return b_factor


def _potrf_b_update(nt, nb, pshp, dt):
    def b_update(t):
        pk_ = t.data("PK", dt, pshp)
        jj = int(t.data("JS", np.int32, (1,))[0])
        pj_ = t.data("PJ", dt, pshp)
        off = jj * nb
        pj_ -= pk_ @ pk_[off:off + nb].T
    return b_update


def build_potrf_panels(ctx: pt.Context, A: TwoDimBlockCyclic,
                       dev: Optional[TpuDevice] = None,
                       name: str = "A") -> pt.Taskpool:
    """Panel-granular Cholesky taskpool.  `A` must be a single block row
    of N x nb panels: TwoDimBlockCyclic(N, N, N, nb) registered under
    `name`.  Also registers an int32 index collection under
    `name + "_pidx"`."""
    return _build_panel_factorization(
        ctx, A, dev, name, k_panel_factor, k_panel_update,
        _potrf_b_factor, _potrf_b_update)


def k_panel_fwd(p, ks, b):
    """Forward-substitution step on the whole RHS block: solve the
    diagonal rows against L_kk, then eliminate below."""
    import jax
    import jax.numpy as jnp
    nb = p.shape[1]
    off = ks[0] * nb
    lkk = jax.lax.dynamic_slice(p, (off, 0), (nb, nb))
    bk = jax.lax.dynamic_slice(b, (off, 0), (nb, b.shape[1]))
    yk = jax.scipy.linalg.solve_triangular(lkk, bk, lower=True)
    upd = b - jax.lax.dot_general(p, yk, (((1,), (0,)), ((), ())),
                                  preferred_element_type=b.dtype)
    rows = jnp.arange(b.shape[0], dtype=ks.dtype)[:, None]
    # rows above the block keep their solved values; the block row takes
    # y_k; rows below take the eliminated update
    out = jnp.where(rows >= off + nb, upd, b)
    return jax.lax.dynamic_update_slice(out, yk, (off, 0))


def k_panel_bwd(p, ks, b):
    """Backward-substitution step: x_k = L_kk^-T (y_k - L_below^T x_below)."""
    import jax
    import jax.numpy as jnp
    nb = p.shape[1]
    off = ks[0] * nb
    lkk = jax.lax.dynamic_slice(p, (off, 0), (nb, nb))
    # contribution of already-solved rows BELOW the block: P rows below
    # hold L[below, k-block]; mask rows <= off+nb so only solved x rows
    # contribute
    rows = jnp.arange(b.shape[0], dtype=ks.dtype)[:, None]
    xmask = jnp.where(rows >= off + nb, b, jnp.zeros((), b.dtype))
    contrib = jax.lax.dot_general(p, xmask, (((0,), (0,)), ((), ())),
                                  preferred_element_type=b.dtype)
    yk = jax.lax.dynamic_slice(b, (off, 0), (nb, b.shape[1]))
    xk = jax.scipy.linalg.solve_triangular(lkk, yk - contrib, lower=True,
                                           trans="T")
    return jax.lax.dynamic_update_slice(b, xk, (off, 0))


def build_potrs_panels(ctx: pt.Context, A: TwoDimBlockCyclic, B,
                       dev: Optional[TpuDevice] = None,
                       name: str = "A", bname: str = "B") -> pt.Taskpool:
    """Panel-granular triangular solve after build_potrf_panels (the
    dpotrs role; potrf_panels + potrs_panels = posv).  `A` holds the
    factored panels (same collection the factorization ran on); `B` is a
    single-tile (N, nrhs) collection registered under `bname`.  Forward
    substitution walks panels 0..NT-1, backward NT-1..0 — 2*NT tasks,
    each one tall MXU contraction over the whole RHS block.
    Single-rank form (the distributed solve rides the tiled
    algos/trsm.py)."""
    assert A.mt == 1 and A.M == A.mb
    assert A.nodes == 1, \
        "potrs_panels is the single-rank solve (distributed: algos/trsm.py)"
    nt = A.nt
    nb = A.nb
    NN = A.M
    dt = A.dtype
    nrhs = B.nb
    assert B.mt == 1 and B.nt == 1 and B.mb == NN
    assert B.dtype == A.dtype, "A and B dtypes must match"
    pidx_name, _ = _register_pidx(ctx, A, name)
    tp = pt.Taskpool(ctx, globals={"NT": nt - 1})
    k = pt.L("k")
    NT = pt.G("NT")

    fw = tp.task_class("FWD")
    fw.param("k", 0, NT)
    fw.affinity(bname, 0, 0)
    fw.flow("P", "READ", pt.In(pt.Mem(name, 0, k)))
    fw.flow("KS", "READ", pt.In(pt.Mem(pidx_name, k)))
    fw.flow("B", "RW",
            pt.In(pt.Mem(bname, 0, 0), guard=(k == 0)),
            pt.In(pt.Ref("FWD", k - 1, flow="B")),
            pt.Out(pt.Ref("FWD", k + 1, flow="B"), guard=(k < NT)),
            pt.Out(pt.Ref("BWD", NT, flow="B"), guard=(k == NT)))

    bw = tp.task_class("BWD")
    bw.param("k", 0, NT)
    bw.affinity(bname, 0, 0)
    bw.flow("P", "READ", pt.In(pt.Mem(name, 0, k)))
    bw.flow("KS", "READ", pt.In(pt.Mem(pidx_name, k)))
    bw.flow("B", "RW",
            pt.In(pt.Ref("FWD", NT, flow="B"), guard=(k == NT)),
            pt.In(pt.Ref("BWD", k + 1, flow="B"), guard=(k < NT)),
            pt.Out(pt.Ref("BWD", k - 1, flow="B"), guard=(k > 0)),
            pt.Out(pt.Mem(bname, 0, 0), guard=(k == 0)))

    pshp, bshp = (NN, nb), (NN, nrhs)
    for d in as_device_list(dev):
        d.attach(fw, tp, kernel=k_panel_fwd, reads=["P", "KS", "B"],
                 writes=["B"], shapes={"P": pshp, "KS": (1,), "B": bshp},
                 dtypes={"P": np.dtype(dt), "KS": np.dtype(np.int32),
                         "B": np.dtype(dt)}, sync_mem_out=True)
        d.attach(bw, tp, kernel=k_panel_bwd, reads=["P", "KS", "B"],
                 writes=["B"], shapes={"P": pshp, "KS": (1,), "B": bshp},
                 dtypes={"P": np.dtype(dt), "KS": np.dtype(np.int32),
                         "B": np.dtype(dt)}, sync_mem_out=True)

    def b_fwd(t):
        p = t.data("P", dt, pshp)
        kk = int(t.data("KS", np.int32, (1,))[0])
        b = t.data("B", dt, bshp)
        off = kk * nb
        yk = np.linalg.solve(p[off:off + nb], b[off:off + nb])
        b[off:off + nb] = yk
        b[off + nb:] -= p[off + nb:] @ yk

    def b_bwd(t):
        p = t.data("P", dt, pshp)
        kk = int(t.data("KS", np.int32, (1,))[0])
        b = t.data("B", dt, bshp)
        off = kk * nb
        lkk = p[off:off + nb]
        contrib = p[off + nb:].T @ b[off + nb:]
        b[off:off + nb] = np.linalg.solve(lkk.T, b[off:off + nb] - contrib)

    fw.body(b_fwd)
    bw.body(b_bwd)
    return tp


def run_potrf(ctx, A, dev=None):
    tp = build_potrf(ctx, A, dev)
    tp.run()
    tp.wait()
    devs = as_device_list(dev)
    for d in devs:
        d.flush()


def potrf_flops(N: int) -> float:
    return N ** 3 / 3.0 + N ** 2 / 2.0 + N / 6.0
