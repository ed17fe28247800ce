"""The panel update computes only the rows a factorization reads.

k_panel_update(pk, js, pj) gives the rows of panel j at and below its
diagonal block, j nb, the full-height pj - pk bj^T; the rows above come
back bit for bit.  This holds for the single-lane form, for a vmapped
wave with pk shared or per lane, and through the device manager's fused
programs, whichever lanes share the wave: consecutive js, js a stride
apart as on Q = 2 ranks, padded duplicate lanes, j at the last block,
one lane."""
import numpy as np
import pytest

from parsec_tpu.algos import potrf

# (N, NB): 16 row blocks of 64 rows, two per panel block; and an N that
# 16 does not divide into whole blocks of NB rows (row blocks of 15)
SHAPES = [(1024, 32), (240, 24)]
# lanes' js, by the wave they model (indices into the panel's blocks,
# negative from the last)
LANES = {"consecutive": [3, 4, 5, 6],
         "stride_q2": [3, 5, 7, 9],
         "padded": [3, 4, 5, 5, 5, 5, 5, 5],
         "last_block": [-1],
         "one_lane": [4]}


def _full_height(pk, js, pj):
    """The update over all N rows, as the kernel computed it before."""
    import jax
    nb = pk.shape[1]
    bj = jax.lax.dynamic_slice(pk, (js[0] * nb, 0), (nb, nb))
    return pj - jax.lax.dot_general(pk, bj, (((1,), (1,)), ((), ())),
                                    preferred_element_type=pj.dtype,
                                    precision="highest")


def _panel(rng, n, nb, k):
    """A factored-looking panel k: rows above k nb zero."""
    p = rng.standard_normal((n, nb)).astype(np.float32)
    p[:k * nb] = 0
    return p


def _wave(n, nb, lanes, seed=0):
    """pk shared (k below every lane's j), per-lane pks with ks of their
    own, the lanes' js [L, 1] and their panels pj [L, n, nb]."""
    rng = np.random.default_rng(seed)
    nt = n // nb
    js = np.array([j % nt for j in lanes], dtype=np.int32)
    k = int(js.min()) - 1
    pk = _panel(rng, n, nb, k)
    pks = np.stack([_panel(rng, n, nb, int(rng.integers(0, j)))
                    for j in js])
    pj = rng.standard_normal((len(js), n, nb)).astype(np.float32)
    return pk, pks, js[:, None], pj


def _check(out, want, pj, js, nb):
    """Rows from j nb down agree with the full-height update to fp32
    tolerance; rows above are pj's, bit for bit."""
    out, want = np.asarray(out), np.asarray(want)
    scale = np.abs(want).max()
    for lane, j in enumerate(js[:, 0]):
        off = int(j) * nb
        np.testing.assert_allclose(out[lane, off:], want[lane, off:],
                                   rtol=1e-5, atol=1e-5 * scale)
        assert out[lane, :off].tobytes() == pj[lane, :off].tobytes(), lane


@pytest.mark.parametrize("n,nb", SHAPES)
@pytest.mark.parametrize("lanes", LANES.values(), ids=LANES.keys())
def test_single_and_vmapped_waves_update_only_their_rows(n, nb, lanes):
    import jax
    pk, pks, js, pj = _wave(n, nb, lanes)
    full = jax.vmap(_full_height, in_axes=(None, 0, 0))(pk, js, pj)
    single = jax.jit(potrf.k_panel_update)
    one = np.stack([np.asarray(single(pk, js[i], pj[i]))
                    for i in range(len(js))])
    _check(one, full, pj, js, nb)
    shared = jax.jit(jax.vmap(potrf.k_panel_update, in_axes=(None, 0, 0)))
    _check(shared(pk, js, pj), full, pj, js, nb)
    per_lane = jax.jit(jax.vmap(potrf.k_panel_update))
    _check(per_lane(pks, js, pj),
           jax.vmap(_full_height)(pks, js, pj), pj, js, nb)


def test_a_lanes_rows_do_not_depend_on_its_wave():
    """Lane j = 6 in waves that start at 1 or at 6, of 2 or 4 lanes: its
    rows at and below 6 nb are bit for bit the same, and agree with the
    single-lane form to fp32 tolerance."""
    import jax
    n, nb = 1024, 32
    pk, _, _, _ = _wave(n, nb, [1])
    pj = np.random.default_rng(1).standard_normal((n, nb)).astype(
        np.float32)
    wave = jax.jit(jax.vmap(potrf.k_panel_update, in_axes=(None, 0, 0)))
    rows = []
    for js in ([1, 6], [6, 7, 8, 9], [6, 6], [1, 2, 3, 6]):
        out = np.asarray(wave(pk, np.array(js, np.int32)[:, None],
                              np.stack([pj] * len(js))))
        rows.append(out[js.index(6), 6 * nb:])
        assert rows[-1].tobytes() == rows[0].tobytes(), js
    alone = np.asarray(jax.jit(potrf.k_panel_update)(
        pk, np.array([6], np.int32), pj))
    np.testing.assert_allclose(alone[6 * nb:], rows[0], rtol=1e-5,
                               atol=1e-5 * np.abs(rows[0]).max())


def test_the_loop_stays_a_loop_under_vmap():
    """A vmapped wave lowers to one while loop over row blocks with
    unbatched bounds, whose dot runs at HIGHEST."""
    import jax
    n, nb = 1024, 32
    pk, _, js, pj = _wave(n, nb, LANES["consecutive"])
    text = jax.jit(jax.vmap(potrf.k_panel_update, in_axes=(None, 0, 0))) \
        .lower(pk, js, pj).as_text()
    assert text.count("stablehlo.while") == 1
    assert "HIGHEST" in text
    assert potrf.panel_row_block(32768) == 2048
    assert potrf.panel_row_block(240) == 15


@pytest.mark.parametrize("lanes", LANES.values(), ids=LANES.keys())
def test_fused_wave_programs_update_only_their_rows(lanes):
    """The device manager's programs for a U wave, with the sigs of
    the panel DAG: ("bcast", "idx", "idx"), targets gathered from a
    stack, and ("bcast", "idx", None) with the speculative F(k+1)
    epilogue on lane 0."""
    import jax
    from parsec_tpu.device.tpu import _get_fused, _get_fused_epi
    n, nb = 1024, 32
    pk, _, js, pj = _wave(n, nb, lanes)
    nt = n // nb
    jstack = np.arange(nt, dtype=np.int32)[:, None]
    jidx = js[:, 0].copy()
    full = jax.vmap(_full_height, in_axes=(None, 0, 0))(pk, js, pj)

    stack = np.concatenate([pj[::-1], pj])
    sidx = np.arange(len(js), 2 * len(js), dtype=np.int32)
    gathered = _get_fused(jax, potrf.k_panel_update,
                          ("bcast", "idx", "idx"), single=False)
    _check(gathered(pk, jstack, jidx, stack, sidx), full, pj, js, nb)

    epi = _get_fused_epi(jax, potrf.k_panel_update, ("bcast", "idx", None),
                         False, potrf.k_panel_factor, 0, 1)
    ks = np.array([js[0, 0]], dtype=np.int32)
    # the factored lane needs an SPD diagonal block
    pj = pj.copy()
    d = int(js[0, 0]) * nb
    pj[0, d:d + nb] += 4 * n * np.eye(nb, dtype=np.float32)
    full = jax.vmap(_full_height, in_axes=(None, 0, 0))(pk, js, pj)
    out, factored = epi(pk, jstack, jidx, pj, np.int32(0), ks)
    _check(out, full, pj, js, nb)
    want = np.asarray(jax.jit(potrf.k_panel_factor)(np.asarray(full)[0],
                                                    ks))
    np.testing.assert_allclose(np.asarray(factored), want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())


def test_second_panel_solve_builds_no_program():
    """A panel solve after the first lowers no new program, and its
    factor is right: the row-blocked update adds no program key."""
    import importlib.util
    import os

    import parsec_tpu as pt
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "device", "test_gather_shapes.py")
    spec = importlib.util.spec_from_file_location("gather_shapes", path)
    gs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gs)
    count = gs.Lowerings()
    try:
        with pt.Context(nb_workers=2) as ctx:
            s = gs.Solver(ctx, "panels", 1024, 32, 8)
            s.solve(0)
            built = count.n
            a = s.solve(1)
            assert count.n == built, f"built {count.n - built} programs"
            gs._check_factor(s, a)
            s.dev.stop()
    finally:
        count.close()
