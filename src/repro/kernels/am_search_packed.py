"""Fused associative search over the *packed* 1-bit AM: XOR + popcount.

``am_search.py`` deploys the AM as ±1 float32 — 32 bits per cell, 32x the
paper's Table-I accounting. This kernel is the deployment path that makes
the 1-bit claim literal: the resident AM is the uint8-packed output of
``pack_bits`` (8 cells/byte, LSB-first along D) and queries arrive packed
the same way. Similarity is computed in the bit domain via the Hamming
identity for bipolar vectors

    dot(q, a) = D_valid - 2 * popcount(bits(q) XOR bits(a)),

so the kernel XORs packed bytes, popcounts them with a 3-step SWAR
reduction on the VPU, accumulates Hamming distance across D slabs, and
folds the same running-winner epilogue as ``am_search.py`` — the emitted
(idx, sim) pair is bit-exact with the unpacked kernel (similarities are
integer-valued, exact in float32).

Geometry contract: the grid is

    (B/bB, C/128, Dp/P)       # P = the whole packed axis (padded to 16
                              # bytes) when Dp <= 128, else 128 bytes

A packed query block must be lane-aligned (a multiple of 128 bytes or
the whole packed axis), so one grid step spans up to 1024 dims rather
than exactly one 128x128 array. The IMC cycle count is kept as a
function of shapes (``imc_cycles_for``: one cycle per 128x128 array,
asserted against ``repro.core.imc.cycles`` in tests/test_packed.py), and
the paper's flagship 128x128 AM is still searched in a single step.

Padding semantics, all bit-exact with the unpacked path:
* D tail bits / padded D slabs are packed as 0 in both query and AM, so
  they XOR to 0 and never touch the Hamming count; ``sim`` uses the true
  (static) valid-dim count, matching the zero-padded float kernel.
* Padded C columns are masked to -inf before the winner update.
* Ties resolve first-wins via the strict ``>`` running compare.

``mode="popcount"`` is the bit-domain path described above (pure VPU).
``mode="unpack"`` is the fallback: the packed query and AM blocks are
unpacked one bit plane at a time to ±1 in VMEM (bit b of every byte) and
fed to the MXU, eight (bB, P) x (P, 128) products summing to the same
dot — same outputs, useful where int ops are slow or for cross-checking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.deploy.padding import pad_tiles, round_up
from repro.kernels.am_search import first_argmax

from repro.kernels.pack_bits import pack_bits

Array = jax.Array

TILE = 128          # centroid columns per grid step (one IMC array)
TILE_P = TILE // 8  # packed bytes per 128-dim slab
MAX_DP_BLOCK = 128  # packed bytes per D grid step: one lane row
ROWS = 8            # query rows per popcount step (one sublane tile)

# Batch-tile height: the one free tiling knob (TILE is the IMC-array
# contract). DEFAULT_BLOCK_B is the untuned fallback; TUNE_BLOCK_B is
# the candidate ladder ``kernels.autotune`` searches.
DEFAULT_BLOCK_B = 256
TUNE_BLOCK_B = (64, 128, 256, 512, 1024)


def dp_block(dp: int) -> int:
    """Packed bytes per D grid step: the whole axis (padded to 16 bytes)
    when it fits one lane row, else 128-byte blocks (zero pad bytes
    XOR-cancel, like the tail bits)."""
    return min(round_up(dp, TILE_P), MAX_DP_BLOCK)


def batch_block(block_b: int, b: int) -> int:
    """Batch tile: ``block_b`` or the whole batch, in ROWS multiples."""
    return round_up(min(block_b, max(b, 1)), ROWS)


def _popcount8(v: Array) -> Array:
    """Population count of a byte held in int32, 3-step SWAR."""
    v = v - ((v >> 1) & 0x55)
    v = (v & 0x33) + ((v >> 2) & 0x33)
    return (v + (v >> 4)) & 0x0F


def accumulate_hamming(q_ref, acc_ref, am_rows) -> None:
    """acc_ref += popcount(q XOR am) for one block of packed queries.

    Walks the block ROWS query rows at a time, so the (rows, P, TILE)
    XOR broadcast stays a few vregs — in VMEM and in compile time —
    whatever the batch tile. ``am_rows(rows)`` gives the AM bytes those
    rows meet as int32: (P, TILE) shared by every row, or (ROWS, P,
    TILE) gathered per query.
    """
    def body(r, carry):
        rows = pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS)
        q = q_ref[rows, :].astype(jnp.int32)  # (ROWS, P)
        a = am_rows(rows)
        x = jax.lax.bitwise_xor(q[:, :, None], a)
        acc_ref[rows, :] += jnp.sum(_popcount8(x), axis=1).astype(
            jnp.float32)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // ROWS, body, 0)


def _make_kernel(n_valid_cols: int, n_valid_dims: int, mode: str):
    """Bind static valid counts + compute mode into the kernel body."""

    def kernel(q_ref, am_ref, idx_ref, sim_ref,
               acc_ref, best_sim_ref, best_idx_ref):
        c, d = pl.program_id(1), pl.program_id(2)
        nc, nd = pl.num_programs(1), pl.num_programs(2)

        @pl.when(d == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a = am_ref[...].astype(jnp.int32)  # (P, TILE)
        if mode == "popcount":
            # Hamming accumulation in the bit domain (VPU only).
            accumulate_hamming(q_ref, acc_ref, lambda rows: a[None])
        else:
            # Unpack-in-VMEM fallback: one ±1 bit plane at a time
            # through the MXU. Dims >= n_valid_dims (tail bits and pad
            # bytes) are zeroed on the query side, as in the float
            # kernel's zero padding.
            q = q_ref[...].astype(jnp.int32)  # (bB, P)
            byte = d * q.shape[1] + jax.lax.broadcasted_iota(
                jnp.int32, q.shape, 1)
            for bit in range(8):
                qv = jnp.where(byte * 8 + bit < n_valid_dims,
                               ((q >> bit) & 1) * 2 - 1, 0)
                av = ((a >> bit) & 1) * 2 - 1
                acc_ref[...] += jnp.dot(
                    qv.astype(jnp.bfloat16), av.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)

        @pl.when(d == nd - 1)
        def _fold_winner():
            if mode == "popcount":
                # dot = D_valid - 2 * hamming; integer-exact in float32.
                sims = n_valid_dims - 2.0 * acc_ref[...]
            else:
                sims = acc_ref[...]  # (bB, TILE)
            col = c * TILE + jax.lax.broadcasted_iota(
                jnp.int32, sims.shape, 1)
            neg = jnp.finfo(jnp.float32).min
            sims = jnp.where(col < n_valid_cols, sims, neg)
            blk_best = jnp.max(sims, axis=1)  # (bB,)
            blk_arg = c * TILE + first_argmax(sims)

            @pl.when(c == 0)
            def _first():
                best_sim_ref[...] = blk_best
                best_idx_ref[...] = blk_arg

            @pl.when(c > 0)
            def _update():
                prev_sim = best_sim_ref[...]
                prev_idx = best_idx_ref[...]
                take = blk_best > prev_sim  # strict: first-wins on ties
                best_sim_ref[...] = jnp.where(take, blk_best, prev_sim)
                best_idx_ref[...] = jnp.where(take, blk_arg, prev_idx)

            @pl.when(c == nc - 1)
            def _emit():
                idx_ref[...] = best_idx_ref[...][:, None]
                sim_ref[...] = best_sim_ref[...][:, None]

    return kernel


def pack_rows(x: Array) -> Array:
    """(B, D) bipolar -> (B, ceil(D/8)) uint8, LSB-first; D-tail bits 0.

    The query-side packer: pads the trailing dimension to a byte boundary
    with -1 (bit 0) so tail bits XOR-cancel against the identically padded
    AM. Shares its bit layout with ``pack_bits`` / ``ref.pack_bits``.
    """
    x = pad_tiles(x.astype(jnp.float32), 1, 8, value=-1.0)
    return pack_bits(x)


@functools.partial(jax.jit, static_argnames=(
    "n_dims", "n_cols", "block_b", "mode", "interpret"))
def am_search_packed(q_packed: Array, am_packed_t: Array, *,
                     n_dims: int, n_cols: int | None = None,
                     block_b: int = DEFAULT_BLOCK_B,
                     mode: str = "popcount",
                     interpret: bool | None = None,
                     ) -> tuple[Array, Array]:
    """Fused associative search over the packed 1-bit AM.

    Args:
      q_packed: (B, Dp) uint8 queries, Dp = ceil(D/8), packed LSB-first
        along D (``pack_rows``); tail bits must be 0.
      am_packed_t: (Dp, C) uint8 transposed packed AM (column c =
        centroid c) — ``pack_rows(am).T`` for a (C, D) bipolar AM.
      n_dims: true (unpacked, unpadded) hypervector dimension D.
      n_cols: true centroid count; defaults to am_packed_t.shape[1].
      block_b: query-batch tile height.
      mode: "popcount" (XOR + SWAR popcount, VPU) or "unpack"
        (unpack-in-VMEM ±1 slabs through the MXU).
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (best_idx, best_sim): (B,) int32 winning centroid per query and
      (B,) float32 its ±1-domain dot similarity — bit-exact with
      ``am_search.am_search`` on the corresponding unpacked operands.
    """
    if mode not in ("popcount", "unpack"):
        raise ValueError(f"bad mode: {mode!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dp = q_packed.shape
    dp2, c = am_packed_t.shape
    assert dp == dp2, (q_packed.shape, am_packed_t.shape)
    if n_cols is None:
        n_cols = c
    if not dp * 8 >= n_dims > (dp - 1) * 8:
        raise ValueError(f"n_dims={n_dims} inconsistent with Dp={dp}")

    p = dp_block(dp)
    bb = batch_block(block_b, b)
    # Zero pad bytes: padded dims XOR to 0 in both operands.
    qp = pad_tiles(q_packed, bb, p)
    ap = pad_tiles(am_packed_t, p, TILE)
    gb = qp.shape[0] // bb
    gc = ap.shape[1] // TILE
    gd = qp.shape[1] // p

    idx, sim = pl.pallas_call(
        _make_kernel(n_cols, n_dims, mode),
        grid=(gb, gc, gd),
        in_specs=[
            pl.BlockSpec((bb, p), lambda i, cc, d: (i, d)),
            pl.BlockSpec((p, TILE), lambda i, cc, d: (d, cc)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, cc, d: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, cc, d: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((qp.shape[0], 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, TILE), jnp.float32),
            pltpu.VMEM((bb,), jnp.float32),
            pltpu.VMEM((bb,), jnp.int32),
        ],
        name="am_search_packed",
        interpret=interpret,
    )(qp, ap)
    return idx[:b, 0], sim[:b, 0]


def imc_cycles_for(am_packed_t_shape: tuple) -> int:
    """(C/128)*(Dp/16) 128x128-array passes per batch tile. One 16-byte
    packed slab covers 128 unpacked dims, so this equals the unpacked
    kernel's (C/128)*(D/128) and must equal
    ``repro.core.imc.map_memhd(...).cycles`` — the packed deployment
    keeps the paper's cycle accounting. A function of shapes: a grid
    step may span up to 8 arrays along D."""
    dp, c = am_packed_t_shape
    return (-(-dp // TILE_P)) * (-(-c // TILE))
