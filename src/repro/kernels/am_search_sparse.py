"""Fine pass of the hierarchical AM search: shortlisted tiles + top-k.

Second stage of the coarse-to-fine pipeline (first stage:
``am_shortlist``). The AM has been physically permuted offline so every
cluster owns a contiguous run of 128-column packed tiles inside one
``am_search_packed``-contract slab (``deploy/hierarchical.build_layout``).
A query therefore only needs the tiles of its S shortlisted clusters:

  1. ``expand_shortlist_tiles`` turns each query's (S,) cluster shortlist
     into a fixed-shape (S * max_tiles,) tile-index list, padding short
     clusters with the slab's trailing all-invalid *null tile*;
  2. the slab is viewed tile-major, (n_tiles, Dp, 128), so each tile is
     one contiguous run of bytes, and its centroid ids as (n_tiles, 128),
     held whole in VMEM (4 bytes a column);
  3. for each (query block, tile slot) grid step the Pallas kernel reads
     the block's row of the tile table into SMEM and copies every row's
     tile out of the slab by DMA into a double-buffered VMEM block — the
     copies for the next step run under the popcount of this one; slots
     that point at the null tile copy nothing. It scans the block with
     the same XOR + SWAR-popcount accumulation as ``am_search_packed``
     and a fused *streaming top-k* epilogue (``topk_select`` merge per
     tile) — so serving can return k candidates, not just an argmax.

Nothing per query is materialised in HBM: the kernel reads each
shortlisted tile straight from the resident slab. Cost per query is
S * max_tiles tiles instead of C/128 — sublinear in C once G ~ sqrt(C)
— while keeping the flat kernel's batch tiling (``block_b`` queries
share each grid step). ``gather_shortlist`` is the same selection as a
plain XLA take, for the off-TPU oracle path.

Ordering is (-similarity, ORIGINAL centroid id): the id of each column
is the centroid's pre-permutation index, and ties resolve toward the
lower id — exactly the flat scan's first-wins compare over the original
column order. That is the degenerate contract: with S = G the
shortlisted set covers every centroid and (idx, sim) at k=1 is
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
    ROWS, TILE, accumulate_hamming, batch_block, dp_block)
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

    The XLA form of the kernel's tile reads, for the off-TPU oracle
    path. am_packed_t: (Dp, Ctot) uint8 permuted packed slab; col_ids:
    (Ctot,) int32 original centroid id per slab column (-1 = padding);
    tiles: (B, T) int32 tile indices. Returns ((B, Dp, T*128) uint8
    gathered tiles, (B, T*128) int32 gathered ids).
    """
    b, t = tiles.shape
    cols = (tiles[:, :, None] * TILE
            + jnp.arange(TILE, dtype=jnp.int32)).reshape(b, t * TILE)
    gathered = jnp.moveaxis(jnp.take(am_packed_t, cols, axis=1), 1, 0)
    return gathered, jnp.take(col_ids, cols, axis=0)


def _make_kernel(n_valid_dims: int, k: int, bb: int, p: int,
                 null_tile: int):
    def kernel(tiles_ref, next_tiles_ref, q_ref, slab_ref, ids_ref,
               idx_ref, sim_ref, buf_ref, sem_ref, acc_ref, blk_ids_ref,
               best_sim_ref, best_idx_ref):
        i, t, d = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        nt, nd = pl.num_programs(1), pl.num_programs(2)
        # Grid steps run in order; step s computes from buffer s % 2
        # while the copies for step s + 1 fill the other one.
        step = (i * nt + t) * nd + d
        n_steps = pl.num_programs(0) * nt * nd
        slot = step % 2

        def for_each_copy(tiles, dd, buf, act):
            """act(copy) for every row whose tile (``tiles[r]``) is not
            the null tile: its bytes at D block ``dd`` land in row r of
            buffer ``buf``. A null slot copies nothing; its ids are -1,
            so whatever its buffer row holds is masked."""
            def body(r, carry):
                tile = tiles[r]

                @pl.when(tile != null_tile)
                def _():
                    act(pltpu.make_async_copy(
                        slab_ref.at[tile, pl.ds(dd * p, p), :],
                        buf_ref.at[buf, r], sem_ref.at[buf]))
                return carry

            jax.lax.fori_loop(0, bb, body, 0)

        @pl.when(step == 0)
        def _prime():
            for_each_copy(tiles_ref, d, slot, lambda c: c.start())

        @pl.when(step + 1 < n_steps)
        def _prefetch_next():
            for_each_copy(next_tiles_ref, (d + 1) % nd, 1 - slot,
                          lambda c: c.start())

        for_each_copy(tiles_ref, d, slot, lambda c: c.wait())

        @pl.when(d == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # (ROWS, P, TILE): each query row meets its own tile.
        accumulate_hamming(
            q_ref, acc_ref,
            lambda rows: buf_ref[slot, rows, :, :].astype(jnp.int32))

        @pl.when(d == nd - 1)
        def _fold_topk():
            # Each row's original centroid ids, ROWS rows at a time.
            def gather_ids(g, carry):
                r0 = pl.multiple_of(g * ROWS, ROWS)
                blk_ids_ref[pl.ds(r0, ROWS), :] = jnp.concatenate(
                    [ids_ref[pl.ds(tiles_ref[r0 + j], 1), :]
                     for j in range(ROWS)], axis=0)
                return carry

            jax.lax.fori_loop(0, bb // ROWS, gather_ids, 0)
            ids = blk_ids_ref[...]  # (bB, TILE)
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


def _search_tiles(q_packed: Array, slab_tiles: Array, tile_ids: Array,
                  table: Array, *, n_dims: int, k: int, block_b: int,
                  interpret: bool | None) -> tuple[Array, Array]:
    """The kernel over a tile-major slab.

    slab_tiles: (n_tiles, Dp, 128) uint8, its LAST tile the null tile;
    tile_ids: (n_tiles, 128) int32 original centroid id per column;
    table: (B, T) int32 tile index per (query, tile slot).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dp = q_packed.shape
    n_tiles, dp2, _ = slab_tiles.shape
    assert dp == dp2, (q_packed.shape, slab_tiles.shape)
    assert tile_ids.shape == (n_tiles, TILE), tile_ids.shape
    assert table.shape[0] == b, (table.shape, q_packed.shape)
    if not dp * 8 >= n_dims > (dp - 1) * 8:
        raise ValueError(f"n_dims={n_dims} inconsistent with Dp={dp}")
    if k < 1:
        raise ValueError(f"k={k} must be >= 1")

    null_tile = n_tiles - 1
    p = dp_block(dp)
    bb = batch_block(block_b, b)
    qp = pad_tiles(q_packed, bb, p)
    bpad, dpad = qp.shape[0] - b, qp.shape[1] - dp
    # Zero pad bytes XOR-cancel; padded rows read the null tile (no
    # copy, ids -1) and are sliced off.
    st = jnp.pad(slab_tiles, ((0, 0), (0, dpad), (0, 0)))
    gb = qp.shape[0] // bb
    gt = table.shape[1]
    gd = qp.shape[1] // p
    # Row (t, i) of the table holds the tiles of slot t for query block
    # i, so a grid step reads bB scalars into SMEM, whatever B and T.
    tbl = jnp.pad(table, ((0, bpad), (0, 0)), constant_values=null_tile)
    tbl = tbl.T.reshape(gt, gb, 1, bb)

    def next_block(i, t, d):
        """Table row of the step after (i, t, d): the tiles whose
        copies step (i, t, d) starts (kept in range at the last step,
        which starts none)."""
        last_d, last_t = d == gd - 1, t == gt - 1
        nxt_t = jnp.where(last_d, jnp.where(last_t, 0, t + 1), t)
        nxt_i = jnp.where(last_d & last_t, jnp.minimum(i + 1, gb - 1), i)
        return nxt_t, nxt_i, 0, 0

    idx, sim = pl.pallas_call(
        _make_kernel(n_dims, k, bb, p, null_tile),
        grid=(gb, gt, gd),
        in_specs=[
            pl.BlockSpec((None, None, None, bb),
                         lambda i, t, d: (t, i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, None, bb), next_block,
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, p), lambda i, t, d: (i, d)),
            pl.BlockSpec(memory_space=pl.ANY),      # slab stays in HBM
            pl.BlockSpec(memory_space=pltpu.VMEM),  # ids, whole
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
            pltpu.VMEM((2, bb, p, TILE), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((bb, TILE), jnp.float32),
            pltpu.VMEM((bb, TILE), jnp.int32),
            pltpu.VMEM((bb, k), jnp.float32),
            pltpu.VMEM((bb, k), jnp.int32),
        ],
        # Two (bB, P, 128) tile buffers: past bB = 256 at P = 128 they
        # outgrow the 16 MiB default.
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="am_search_sparse_gathered",
        interpret=interpret,
    )(tbl, tbl, qp, st, tile_ids)
    return idx[:b], sim[:b]


@functools.partial(jax.jit, static_argnames=(
    "n_dims", "k", "block_b", "interpret"))
def am_search_sparse_gathered(q_packed: Array, tiles_packed: Array,
                              tile_ids: Array, *, n_dims: int, k: int,
                              block_b: int = DEFAULT_BLOCK_B,
                              interpret: bool | None = None,
                              ) -> tuple[Array, Array]:
    """Streaming top-k search over pre-gathered per-query tiles.

    The same kernel as ``am_search_sparse``: the gathered operand is
    viewed as a tile-major slab of B * T tiles (plus a null tile), read
    through the identity table ``arange(B * T).reshape(B, T)``.

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
    b, dp = q_packed.shape
    b2, dp2, tc = tiles_packed.shape
    assert (b, dp) == (b2, dp2), (q_packed.shape, tiles_packed.shape)
    assert tile_ids.shape == (b, tc), (tile_ids.shape, tiles_packed.shape)
    if tc % TILE != 0:
        raise ValueError(f"gathered columns {tc} not a multiple of {TILE}")
    t = tc // TILE
    slab = jnp.concatenate([
        tiles_packed.reshape(b, dp, t, TILE).transpose(0, 2, 1, 3)
        .reshape(b * t, dp, TILE),
        jnp.zeros((1, dp, TILE), jnp.uint8)])
    ids = jnp.concatenate([tile_ids.reshape(b * t, TILE),
                           jnp.full((1, TILE), -1, jnp.int32)])
    table = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
    return _search_tiles(q_packed, slab, ids, table, n_dims=n_dims, k=k,
                         block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "n_dims", "k", "max_tiles", "block_b", "interpret"))
def am_search_sparse(q_packed: Array, am_packed_t: Array, col_ids: Array,
                     shortlist: Array, tile_start: Array,
                     tile_count: Array, *, n_dims: int, k: int,
                     max_tiles: int, block_b: int = DEFAULT_BLOCK_B,
                     interpret: bool | None = None) -> tuple[Array, Array]:
    """Expand + kernel: the full fine pass on the layout slab.

    am_packed_t is the permuted padded slab whose LAST 128-column tile is
    the all-invalid null tile (``build_layout`` appends it); col_ids maps
    slab columns back to original centroid ids (-1 = padding). The slab
    is viewed tile-major (one relayout of the slab a call); the kernel
    reads the shortlisted tiles from it by DMA.
    """
    dp, ctot = am_packed_t.shape
    n_tiles = ctot // TILE
    tiles = expand_shortlist_tiles(
        shortlist, tile_start, tile_count,
        max_tiles=max_tiles, null_tile=n_tiles - 1)
    slab = am_packed_t.reshape(dp, n_tiles, TILE).transpose(1, 0, 2)
    return _search_tiles(q_packed, slab, col_ids.reshape(n_tiles, TILE),
                         tiles, n_dims=n_dims, k=k, block_b=block_b,
                         interpret=interpret)
