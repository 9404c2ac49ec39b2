"""Fine pass of the hierarchical AM search: shortlisted tiles + top-k.

Second stage of the coarse-to-fine pipeline (first stage:
``am_shortlist``). The AM has been physically permuted offline so every
cluster owns a contiguous run of 128-column packed tiles inside one
``am_search_packed``-contract slab (``deploy/hierarchical.build_layout``).
A query therefore only needs the tiles of its S shortlisted clusters:

  1. ``expand_shortlist_tiles`` turns each query's (S,) cluster shortlist
     into a fixed-shape (S * max_tiles,) tile-index list, padding short
     clusters with the slab's trailing all-invalid *null tile*;
  2. ``gather_shortlist`` gathers those tiles (and their original
     centroid ids) out of the slab — a plain XLA take, fixed shapes, so
     the whole pipeline stays jittable;
  3. the Pallas kernel scans the gathered (B, Dp, T*128) slab with the
     same XOR + SWAR-popcount accumulation as ``am_search_packed`` and a
     fused *streaming top-k* epilogue (``topk_select`` merge per tile) —
     so serving can return k candidates, not just an argmax.

Cost per query is S * max_tiles tiles instead of C/128 — sublinear in C
once G ~ sqrt(C) — while keeping the flat kernel's batch tiling (the
gather runs in XLA, so ``block_b`` queries still share each grid step).

Ordering is (-similarity, ORIGINAL centroid id): the id gathered with
each column is the centroid's pre-permutation index, and ties resolve
toward the lower id — exactly the flat scan's first-wins compare over
the original column order. That is the degenerate contract: with S = G
the gathered set covers every centroid and (idx, sim) at k=1 is
bit-exact with ``am_search_packed``. Columns whose id is -1 (cluster
padding / null tile) are masked out; output slots with no candidate
left emit id -1 and sim float32-min, matching ``ref.am_search_sparse``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.deploy.padding import pad_tiles

from repro.kernels.am_search_packed import (
    TILE, accumulate_hamming, batch_block, dp_block)
from repro.kernels.am_shortlist import topk_select

Array = jax.Array

DEFAULT_BLOCK_B = 256
TUNE_BLOCK_B = (64, 128, 256, 512, 1024)

_NEG = float(jnp.finfo(jnp.float32).min)
_SENT = int(jnp.iinfo(jnp.int32).max)


def expand_shortlist_tiles(shortlist: Array, tile_start: Array,
                           tile_count: Array, *, max_tiles: int,
                           null_tile: int) -> Array:
    """(B, S) cluster shortlist -> (B, S * max_tiles) slab tile indices.

    Every cluster contributes a fixed ``max_tiles`` slots (fixed shapes
    keep this jittable); slots past a cluster's real ``tile_count`` point
    at ``null_tile`` — the slab's trailing all-invalid tile, whose
    columns carry id -1 and are masked by the kernel.
    """
    j = jnp.arange(max_tiles, dtype=jnp.int32)
    ts = tile_start[shortlist]  # (B, S)
    tc = tile_count[shortlist]
    tiles = ts[:, :, None] + j[None, None, :]  # (B, S, max_tiles)
    tiles = jnp.where(j[None, None, :] < tc[:, :, None], tiles, null_tile)
    return tiles.reshape(shortlist.shape[0], -1)


def gather_shortlist(am_packed_t: Array, col_ids: Array, tiles: Array,
                     ) -> tuple[Array, Array]:
    """Gather per-query tiles (and their centroid ids) from the slab.

    am_packed_t: (Dp, Ctot) uint8 permuted packed slab; col_ids: (Ctot,)
    int32 original centroid id per slab column (-1 = padding); tiles:
    (B, T) int32 tile indices. Returns ((B, Dp, T*128) uint8 gathered
    tiles, (B, T*128) int32 gathered ids).
    """
    b, t = tiles.shape
    cols = (tiles[:, :, None] * TILE
            + jnp.arange(TILE, dtype=jnp.int32)).reshape(b, t * TILE)
    gathered = jnp.moveaxis(jnp.take(am_packed_t, cols, axis=1), 1, 0)
    return gathered, jnp.take(col_ids, cols, axis=0)


def _make_kernel(n_valid_dims: int, k: int):
    def kernel(q_ref, tiles_ref, ids_ref, idx_ref, sim_ref,
               acc_ref, best_sim_ref, best_idx_ref):
        t, d = pl.program_id(1), pl.program_id(2)
        nt, nd = pl.num_programs(1), pl.num_programs(2)

        @pl.when(d == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # (ROWS, P, TILE): each query row meets its own gathered tiles.
        accumulate_hamming(
            q_ref, acc_ref,
            lambda rows: tiles_ref[rows, :, :].astype(jnp.int32))

        @pl.when(d == nd - 1)
        def _fold_topk():
            ids = ids_ref[...]  # (bB, TILE) original centroid ids
            valid = ids >= 0
            sims = jnp.where(valid,
                             n_valid_dims - 2.0 * acc_ref[...], _NEG)
            sel = jnp.where(valid, ids, _SENT)
            blk_s, blk_i = topk_select(sims, sel, k)

            @pl.when(t == 0)
            def _first():
                best_sim_ref[...] = blk_s
                best_idx_ref[...] = blk_i

            @pl.when(t > 0)
            def _merge():
                ms, mi = topk_select(
                    jnp.concatenate([best_sim_ref[...], blk_s], axis=1),
                    jnp.concatenate([best_idx_ref[...], blk_i], axis=1),
                    k)
                best_sim_ref[...] = ms
                best_idx_ref[...] = mi

            @pl.when(t == nt - 1)
            def _emit():
                bs = best_sim_ref[...]
                bi = best_idx_ref[...]
                idx_ref[...] = jnp.where(bs > _NEG, bi, -1)
                sim_ref[...] = bs

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "n_dims", "k", "block_b", "interpret"))
def am_search_sparse_gathered(q_packed: Array, tiles_packed: Array,
                              tile_ids: Array, *, n_dims: int, k: int,
                              block_b: int = DEFAULT_BLOCK_B,
                              interpret: bool | None = None,
                              ) -> tuple[Array, Array]:
    """Streaming top-k search over pre-gathered per-query tiles.

    Args:
      q_packed: (B, Dp) uint8 packed queries, tail bits 0.
      tiles_packed: (B, Dp, T*128) uint8 gathered tiles
        (``gather_shortlist``); T*128 must be a multiple of 128.
      tile_ids: (B, T*128) int32 original centroid id per gathered
        column, -1 for invalid (padding / null-tile) columns.
      n_dims: true hypervector dimension D.
      k: number of candidates to return (static).
      block_b: query-batch tile height.
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (idx, sims): (B, k) int32 original centroid ids and (B, k) float32
      similarities, ordered by (-sim, id); exhausted slots are
      (-1, float32-min). Bit-exact with ``ref.am_search_sparse``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dp = q_packed.shape
    b2, dp2, tc = tiles_packed.shape
    assert (b, dp) == (b2, dp2), (q_packed.shape, tiles_packed.shape)
    assert tile_ids.shape == (b, tc), (tile_ids.shape, tiles_packed.shape)
    if tc % TILE != 0:
        raise ValueError(f"gathered columns {tc} not a multiple of {TILE}")
    if not dp * 8 >= n_dims > (dp - 1) * 8:
        raise ValueError(f"n_dims={n_dims} inconsistent with Dp={dp}")
    if k < 1:
        raise ValueError(f"k={k} must be >= 1")

    p = dp_block(dp)
    bb = batch_block(block_b, b)
    qp = pad_tiles(q_packed, bb, p)
    bpad, dpad = qp.shape[0] - b, qp.shape[1] - dp
    # Zero pad bytes XOR-cancel; padded rows are sliced off; padded ids
    # are -1 so no padding column can ever enter a top-k.
    tp = jnp.pad(tiles_packed, ((0, bpad), (0, dpad), (0, 0)))
    ip = jnp.pad(tile_ids, ((0, bpad), (0, 0)), constant_values=-1)
    gb = qp.shape[0] // bb
    gt = tc // TILE
    gd = qp.shape[1] // p

    idx, sim = pl.pallas_call(
        _make_kernel(n_dims, k),
        grid=(gb, gt, gd),
        in_specs=[
            pl.BlockSpec((bb, p), lambda i, t, d: (i, d)),
            pl.BlockSpec((bb, p, TILE), lambda i, t, d: (i, d, t)),
            pl.BlockSpec((bb, TILE), lambda i, t, d: (i, t)),
        ],
        out_specs=[
            pl.BlockSpec((bb, k), lambda i, t, d: (i, 0)),
            pl.BlockSpec((bb, k), lambda i, t, d: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.int32),
            jax.ShapeDtypeStruct((qp.shape[0], k), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, TILE), jnp.float32),
            pltpu.VMEM((bb, k), jnp.float32),
            pltpu.VMEM((bb, k), jnp.int32),
        ],
        # The gathered tiles are per query, (bB, P, 128) bytes a block
        # (double-buffered), unlike the flat kernel's shared AM block:
        # past bB = 256 at P = 128 they outgrow the 16 MiB default.
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="am_search_sparse_gathered",
        interpret=interpret,
    )(qp, tp, ip)
    return idx[:b], sim[:b]


@functools.partial(jax.jit, static_argnames=(
    "n_dims", "k", "max_tiles", "block_b", "interpret"))
def am_search_sparse(q_packed: Array, am_packed_t: Array, col_ids: Array,
                     shortlist: Array, tile_start: Array,
                     tile_count: Array, *, n_dims: int, k: int,
                     max_tiles: int, block_b: int = DEFAULT_BLOCK_B,
                     interpret: bool | None = None) -> tuple[Array, Array]:
    """Expand + gather + kernel: the full fine pass on the layout slab.

    am_packed_t is the permuted padded slab whose LAST 128-column tile is
    the all-invalid null tile (``build_layout`` appends it); col_ids maps
    slab columns back to original centroid ids (-1 = padding).
    """
    null_tile = am_packed_t.shape[1] // TILE - 1
    tiles = expand_shortlist_tiles(
        shortlist, tile_start, tile_count,
        max_tiles=max_tiles, null_tile=null_tile)
    gathered, ids = gather_shortlist(am_packed_t, col_ids, tiles)
    return am_search_sparse_gathered(
        q_packed, gathered, ids, n_dims=n_dims, k=k,
        block_b=block_b, interpret=interpret)
