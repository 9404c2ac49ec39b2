"""Compile the main-path Pallas kernels for a TPU v5e chip, without one.

The CPU test suite runs every kernel in interpret mode, which never
checks a tiling against the TPU compiler (Mosaic). These tests lower and
compile each main-path kernel with ``interpret=False`` at the paper's
three geometries (the hierarchical pair at D1024, C ~ 100k) for a
*described* v5e chip, and check that the compiled program holds
the kernel (``tpu_custom_call``). Nothing runs; this guards layouts,
block shapes and VMEM budgets, not results.

The topology is described inside a module-scoped fixture, never while
the module is imported: only one process at a time may load the TPU
compiler library, and the test workers all import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.am_search_imc import am_search_imc
from repro.kernels.am_search_multibit import am_search_multibit
from repro.kernels.am_search_packed import am_search_packed
from repro.kernels.am_search_sparse import am_search_sparse
from repro.kernels.am_shortlist import am_shortlist
from repro.kernels.encode_fused import encode_pack
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.pack_bits import pack_bits
from repro.kernels.qail_update import qail_update

B = 256
# The paper geometries (configs/memhd_paper.py GRIDS / FLAGSHIP) as
# (features, dims, columns); the hierarchical pair runs at D1024 only.
GEOMETRIES = {"F784_D128_C128": (784, 128, 128),
              "F617_D512_C128": (617, 512, 128),
              "F784_D1024_C1024": (784, 1024, 1024)}
G, S, MAX_TILES = 448, 8, 4
C_SLAB = 100_352 + 128 * 64  # C ~ 100k laid out in cluster tiles
# The served hierarchical shape: 1,024-row batches over a 131,073-label
# slab of 1,297 cluster tiles plus the null tile, G = 507, S = 8, groups
# of at most 3 tiles. Its exact configuration (S = G) gives 1,521 tile
# slots a row: the tile table reaches SMEM a block row at a time, so
# neither B nor S bounds it.
SERVED_B, SERVED_G, SERVED_MAX_TILES = 1024, 507, 3
SERVED_SLAB = 1298 * 128

f32, u8, i32, bf16 = jnp.float32, jnp.uint8, jnp.int32, jnp.bfloat16


def kernels(f: int, d: int, c: int) -> dict:
    """name -> (kernel call, operand shapes/dtypes) at one geometry."""
    dp = -(-d // 8)
    tiles = (-(-d // 128), -(-c // 128))
    out = {
        "pack_rows": (lambda x: pack_bits(x, interpret=False),
                      [((B, d), f32)]),
        "encode_pack": (lambda x, m: encode_pack(x, m, interpret=False),
                        [((B, f), f32), ((f, d), f32)]),
        "am_search_packed_popcount": (
            lambda q, a: am_search_packed(q, a, n_dims=d, mode="popcount",
                                          interpret=False),
            [((B, dp), u8), ((dp, c), u8)]),
        "am_search_packed_unpack": (
            lambda q, a: am_search_packed(q, a, n_dims=d, mode="unpack",
                                          interpret=False),
            [((B, dp), u8), ((dp, c), u8)]),
        "am_search_imc": (
            lambda q, a, o: am_search_imc(q, a, o, adc_bits=8,
                                          interpret=False),
            [((B, d), f32), ((d, c), f32), (tiles, f32)]),
        "am_search_multibit_4bit": (
            lambda q, a, o: am_search_multibit(q, a, o, cell_bits=4,
                                               interpret=False),
            [((B, d), f32), ((4, dp, c), u8), (tiles, f32)]),
        "qail_update": (
            lambda q, u, a, cc, y, m: qail_update(
                q, u, a, cc, y, m, lr=0.0625, interpret=False),
            [((B, d), f32), ((B, d), f32), ((d, c), f32), ((c,), i32),
             ((B,), i32), ((B,), f32)]),
    }
    if d == 1024:
        out["am_shortlist"] = (
            lambda q, a: am_shortlist(q, a, n_dims=d, s=S, interpret=False),
            [((B, dp), u8), ((dp, G), u8)])
        out["am_search_sparse"] = (
            lambda q, a, ids, sl, ts, tc: am_search_sparse(
                q, a, ids, sl, ts, tc, n_dims=d, k=3, max_tiles=MAX_TILES,
                interpret=False),
            [((B, dp), u8), ((dp, C_SLAB), u8), ((C_SLAB,), i32),
             ((B, S), i32), ((G,), i32), ((G,), i32)])
        out["am_search_sparse_served"] = (
            lambda q, a, ids, sl, ts, tc: am_search_sparse(
                q, a, ids, sl, ts, tc, n_dims=d, k=1,
                max_tiles=SERVED_MAX_TILES, interpret=False),
            [((SERVED_B, dp), u8), ((dp, SERVED_SLAB), u8),
             ((SERVED_SLAB,), i32), ((SERVED_B, S), i32),
             ((SERVED_G,), i32), ((SERVED_G,), i32)])
        out["am_search_sparse_exact"] = (
            lambda q, a, ids, sl, ts, tc: am_search_sparse(
                q, a, ids, sl, ts, tc, n_dims=d, k=1,
                max_tiles=SERVED_MAX_TILES, interpret=False),
            [((B, dp), u8), ((dp, SERVED_SLAB), u8),
             ((SERVED_SLAB,), i32), ((B, SERVED_G), i32),
             ((SERVED_G,), i32), ((SERVED_G,), i32)])
    return out


CASES = [(geo, name) for geo, shape in GEOMETRIES.items()
         for name in sorted(kernels(*shape))]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("geometry,name", CASES)
def test_kernel_compiles_for_v5e(geometry, name, one_chip,
                                 no_persistent_cache):
    fn, operands = kernels(*GEOMETRIES[geometry])[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The DeepSeek-V3 front end's kernels at its widths: 4 pooled bf16 rows of
# 7,168 features into D = 1,024, and the held experts' grouped matmuls
# over one pass of 4,096 routed slots (8 held experts, d_ff 2,048).
FRONTEND = {
    "encode_pack_bf16": (lambda x, m: encode_pack(x, m, interpret=False),
                         [((4, 7168), bf16), ((7168, 1024), bf16)]),
    "moe_gmm_gate_up": (
        lambda a, w, g, u: moe_gmm(a, w, g, u, interpret=False),
        [((4096, 7168), bf16), ((8, 7168, 2048), bf16), ((9,), i32),
         ((8, 7168, 2048), bf16)]),
    "moe_gmm_down": (lambda a, w, g: moe_gmm(a, w, g, interpret=False),
                     [((4096, 2048), bf16), ((8, 2048, 7168), bf16),
                      ((9,), i32)]),
}


@pytest.mark.parametrize("name", sorted(FRONTEND))
def test_frontend_kernel_compiles_for_v5e(name, one_chip,
                                          no_persistent_cache):
    fn, operands = FRONTEND[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
