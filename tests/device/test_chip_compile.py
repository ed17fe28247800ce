"""The main path's device programs compile for a v5e at real widths.

Compiled for a DESCRIBED v5e:2x2 topology (no chip attached): the
panel-Cholesky wave executables TpuDevice builds at fp32 N=32768 NB=512,
the tile kernels build_potrf dispatches at NB=512, and the two Pallas
kernels with interpret mode left to the backend — the TPU compiler
refuses here what it would refuse on the chip, at no chip time.  Each
program must fit one chip's 16 GiB.  Nothing runs: no results, no times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import numpy as np
import pytest

HBM = 16 << 30                 # one v5e's HBM
V5E_BYTES_LIMIT = 16909336064  # a v5e's memory_stats()["bytes_limit"]

PANEL_N, TILE_N, NB = 32768, 8192, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """compile(fn, *(shape, dtype)) -> the executable for one v5e, with
    the persistent compile cache off (a TPU entry written here could not
    be read back without a chip)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, np.dtype(d), sharding=one_chip)
                for s, d in specs]
        return fn.lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _panel_lanes():
    """Lanes of the widest update wave TpuDevice emits at N=32768: the
    per-call byte cap spotrf_device sets, over one U task's PK + PJ in +
    PJ out panels, floored to a power of two (_dispatch_group)."""
    from parsec_tpu.device.bench_utils import spotrf_budget
    _, cap = spotrf_budget(V5E_BYTES_LIMIT, PANEL_N * PANEL_N * 4)
    chunk = cap // (3 * PANEL_N * NB * 4 + 4)
    return 1 << (chunk.bit_length() - 1)


def _programs():
    """name -> (jitted program, arg specs), as TpuDevice builds them (the
    sigs are those a CPU run of the same DAG dispatches)."""
    import jax
    from parsec_tpu.algos import potrf
    from parsec_tpu.device.tpu import _get_fused, _get_fused_epi
    from parsec_tpu.ops import flash_attention, rms_norm
    f32, i32 = "float32", "int32"
    nt, lanes = PANEL_N // NB, _panel_lanes()
    panel, gen = (PANEL_N, NB), (nt, PANEL_N, NB)
    tiles, tile = (TILE_N // NB) ** 2, (NB, NB)
    stack = (tiles,) + tile
    return {
        # F(0): the first panel, gathered from the generator's stack
        "panel_factor": (
            _get_fused(jax, potrf.k_panel_factor, ("idx", "idx"),
                       single=True),
            [(gen, f32), ((), i32), ((nt, 1), i32), ((), i32)]),
        # a U(k, *) wave whose lane j = k+1 also factors F(k+1)
        "panel_update_epilogue": (
            _get_fused_epi(jax, potrf.k_panel_update, ("bcast", "idx", None),
                           False, potrf.k_panel_factor, 0, 1),
            [(panel, f32), ((nt, 1), i32), ((lanes,), i32),
             ((lanes,) + panel, f32), ((), i32), ((1,), i32)]),
        # the k = 0 wave, gathering its targets from the generator's stack
        "panel_update_from_generator": (
            _get_fused(jax, potrf.k_panel_update, ("bcast", "idx", "idx"),
                       single=False),
            [(panel, f32), ((nt, 1), i32), ((lanes,), i32), (gen, f32),
             ((lanes,), i32)]),
        "tile_potrf_inv": (
            _get_fused(jax, potrf.k_potrf_inv, ("idx",), single=True),
            [(stack, f32), ((), i32)]),
        "tile_trsm_mm": (
            _get_fused(jax, potrf.k_trsm_mm, ("bcast", "idx"), single=False),
            [(tile, f32), (stack, f32), ((16,), i32)]),
        "tile_syrk": (
            _get_fused(jax, potrf.k_syrk, ("idx", "idx"), single=False),
            [(stack, f32), ((16,), i32), (stack, f32), ((16,), i32)]),
        "tile_gemm": (
            _get_fused(jax, potrf.k_gemm, ("idx", "idx", "idx"),
                       single=False),
            [(stack, f32), ((128,), i32)] * 3),
        "flash_attention": (
            jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)),
            [((1, 4096, 8, 128), "bfloat16")] * 3),
        "rms_norm": (
            jax.jit(lambda x, w: rms_norm(x, w)),
            [((4096, 4096), "bfloat16"), ((4096,), "bfloat16")]),
    }


@pytest.mark.parametrize("name", [
    "panel_factor", "panel_update_epilogue", "panel_update_from_generator",
    "tile_potrf_inv", "tile_trsm_mm", "tile_syrk", "tile_gemm",
    "flash_attention", "rms_norm"])
def test_compiles_for_v5e(compile_for_chip, name):
    fn, specs = _programs()[name]
    exe = compile_for_chip(fn, *specs)
    mem = exe.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM, f"{name}: {used / 2**30:.2f} GiB > 16 GiB"
    if name in ("flash_attention", "rms_norm"):
        # interpret mode is the backend's choice: the Mosaic kernel is in
        assert "tpu_custom_call" in exe.as_text()
