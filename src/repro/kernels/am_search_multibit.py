"""Bit-sliced multi-bit associative search: packed int2/int4 MVM + ADC.

The 1-bit deployment paths bound the accuracy/memory frontier from one
side (``am_search_packed``: 1 bit/cell, binary accuracy) and the float
path from the other (``am_search``: 32 bits/cell, float accuracy). This
kernel opens the region between them: the resident AM is a symmetric
``cell_bits``-bit quantization of the *float* AM shadow, stored as bit
planes packed 8 cells/byte along D (``ref.pack_planes``), and the search
runs on the ``am_search_imc`` tiling/grid contract — one (C, D) grid
step is one physical array pass over multi-level cells.

Bit-sliced MVM, per tile, entirely in VMEM:

    codes are stored as offset codes  u = code + Qmax  in  [0, 2^b - 2]
    (Qmax = 2^(b-1) - 1), one packed bit plane per bit of u.  Each plane
    is unpacked to a {0, 1} float slab and fed to the MXU; the per-plane
    partial sums combine with shifted weights and the offset is removed
    with a single rowsum correction:

        part = sum_p 2^p * (q_tile @ U_p)  -  Qmax * rowsum(q_tile)
             = q_tile @ (u - Qmax)  =  q_tile @ codes        (exact)

    then the ``am_search_imc`` epilogue: per-tile readout drift offset,
    symmetric mid-tread ADC, digital accumulation, and the first-wins
    running-winner fold.

Everything inside the kernel lives in the integer *code* domain: with
bipolar queries every partial sum is an integer bounded by
``Qmax * tile_rows`` (~1024 at b=4, A=128), far below 2^24, so float32
arithmetic is exact and the kernel is bit-for-bit equal to the
``ref.am_search_multibit`` oracle — the same fidelity-parity contract
``am_search_imc`` has. The default ADC clip (``ref.multibit_adc_clip``:
next power of two >= Qmax * tile_rows) keeps the mid-tread step a power
of two, so any ADC with step <= 1 reproduces the un-quantized search
exactly. Dequantized similarities are the caller's job: multiply by the
quantizer scale outside the kernel (argmax is scale-invariant).

Padding semantics: packed D-tail bits are 0, i.e. offset code u = 0 and
effective code -Qmax — harmless because the matching query rows are
zero-padded (the rowsum correction has the same property). Padded C
columns are masked to -inf before the winner update, as everywhere.

Memory: C * D * cell_bits resident bits — 16x (b=2) / 8x (b=4) below
the 32-bit unpacked float AM, while reading out against the float
shadow's accuracy rather than the binarized AM's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.deploy.padding import pad_tiles
from repro.kernels.am_search import first_argmax
from repro.kernels.ref import multibit_adc_clip

Array = jax.Array

# Batch-tile height knob, same ladder as the other search kernels; the
# VMEM ceiling is the per-plane unpacked (tile_rows, tile_cols) slab
# plus the (bb, tile_cols) accumulator.
DEFAULT_BLOCK_B = 256
TUNE_BLOCK_B = (64, 128, 256, 512, 1024)


def _make_kernel(n_valid_cols: int, cell_bits: int, adc_bits: int,
                 adc_clip: float, tile_rows: int, tile_cols: int):
    """Bind static geometry + quantizer + ADC transfer into the body."""
    step = 2.0 * adc_clip / (2 ** adc_bits)
    qmax = float(2 ** (cell_bits - 1) - 1)

    def kernel(q_ref, am_ref, off_ref, idx_ref, sim_ref,
               acc_ref, best_sim_ref, best_idx_ref):
        c, d = pl.program_id(1), pl.program_id(2)
        nc, nd = pl.num_programs(1), pl.num_programs(2)

        @pl.when(d == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[...].astype(jnp.float32)      # (bB, tile_rows)
        slabs = am_ref[...].astype(jnp.int32)   # (bits, tile_rows/8, tc)
        shifts = jnp.arange(8, dtype=jnp.int32)
        # Bit-sliced analog pass: one {0,1} plane per stored bit through
        # the MXU, partial sums combined with shifted weights...
        part = jnp.zeros((q.shape[0], tile_cols), jnp.float32)
        for p in range(cell_bits):
            bits = (slabs[p][:, None, :] >> shifts[:, None]) & 1
            plane = bits.reshape(tile_rows, tile_cols).astype(jnp.float32)
            part += (2.0 ** p) * jnp.dot(
                q, plane, preferred_element_type=jnp.float32)
        # ...minus the offset-code recentering (u = code + Qmax).
        part -= qmax * jnp.sum(q, axis=1, keepdims=True)
        # Readout drift + ADC, then digital accumulation — identical
        # epilogue to am_search_imc, in the code domain.
        part = part + off_ref[d * nc + c]
        part = jnp.clip(part, -adc_clip, adc_clip)
        part = jnp.round(part / step) * step
        acc_ref[...] += part

        @pl.when(d == nd - 1)
        def _fold_winner():
            sims = acc_ref[...]  # (bB, tile_cols)
            col = c * tile_cols + jax.lax.broadcasted_iota(
                jnp.int32, sims.shape, 1)
            neg = jnp.finfo(jnp.float32).min
            sims = jnp.where(col < n_valid_cols, sims, neg)
            blk_best = jnp.max(sims, axis=1)  # (bB,)
            blk_arg = c * tile_cols + first_argmax(sims)

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


@functools.partial(jax.jit, static_argnames=(
    "cell_bits", "tile_rows", "tile_cols", "adc_bits", "adc_clip",
    "block_b", "interpret"))
def am_search_multibit(q: Array, am_planes_t: Array,
                       offsets: Array | None = None, *,
                       cell_bits: int, tile_rows: int = 128,
                       tile_cols: int = 128, adc_bits: int = 16,
                       adc_clip: float | None = None,
                       block_b: int = DEFAULT_BLOCK_B,
                       interpret: bool | None = None,
                       ) -> tuple[Array, Array]:
    """Bit-sliced associative search over the multi-bit packed AM.

    Args:
      q: (B, D) bipolar query hypervectors.
      am_planes_t: (cell_bits, ceil(D/8), C) uint8 offset-code bit
        planes — ``ref.pack_planes(codes + Qmax, cell_bits)`` for a
        (C, D) code matrix from ``repro.core.am.quantize_am``.
      offsets: (ceil(D/tile_rows), ceil(C/tile_cols)) per-tile
        code-domain readout offsets, or None for drift-free readout.
      cell_bits: bits per memory cell (2..8).
      tile_rows / tile_cols: physical array geometry (ImcArrayConfig).
      adc_bits / adc_clip: ADC resolution and full-scale range; clip
        defaults to ``ref.multibit_adc_clip(cell_bits, tile_rows)``.
      block_b: query-batch tile height.
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (best_idx, best_sim): (B,) int32 winning centroid per query and
      (B,) float32 its code-domain ADC-quantized similarity (multiply
      by the quantizer scale for the dequantized value).
    """
    if not 2 <= cell_bits <= 8:
        raise ValueError(f"cell_bits={cell_bits} outside [2, 8]")
    if tile_rows % 8:
        raise ValueError(f"tile_rows={tile_rows} not a byte multiple")
    if adc_clip is None:
        adc_clip = multibit_adc_clip(cell_bits, tile_rows)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dd = q.shape
    n_planes, dp, c = am_planes_t.shape
    if n_planes != cell_bits:
        raise ValueError(
            f"{n_planes} planes for cell_bits={cell_bits}")
    if not dp * 8 >= dd > (dp - 1) * 8:
        raise ValueError(f"D={dd} inconsistent with Dp={dp}")

    bb = min(block_b, max(b, 1))
    tr_p = tile_rows // 8
    qp = pad_tiles(q.astype(jnp.float32), bb, tile_rows)
    gb = qp.shape[0] // bb
    gd = qp.shape[1] // tile_rows
    gc = -(-c // tile_cols)
    # Zero-pad planes: padded cells hold offset code 0; the matching
    # query rows are zero so the recentering stays exact, and padded
    # columns are masked in the winner fold.
    ap = jnp.pad(am_planes_t, ((0, 0), (0, gd * tr_p - dp),
                               (0, gc * tile_cols - c)))
    if offsets is None:
        offsets = jnp.zeros((gd, gc), jnp.float32)
    if offsets.shape != (gd, gc):
        raise ValueError(
            f"offsets shape {offsets.shape} != tile grid {(gd, gc)}")

    idx, sim = pl.pallas_call(
        _make_kernel(c, cell_bits, adc_bits, float(adc_clip),
                     tile_rows, tile_cols),
        grid=(gb, gc, gd),
        in_specs=[
            pl.BlockSpec((bb, tile_rows), lambda i, cc, d: (i, d)),
            pl.BlockSpec((n_planes, tr_p, tile_cols),
                         lambda i, cc, d: (0, d, cc)),
            # Per-tile offsets, flat (d, c) row-major, read as scalars.
            pl.BlockSpec(memory_space=pltpu.SMEM),
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
            pltpu.VMEM((bb, tile_cols), jnp.float32),
            pltpu.VMEM((bb,), jnp.float32),
            pltpu.VMEM((bb,), jnp.int32),
        ],
        name="am_search_multibit",
        interpret=interpret,
    )(qp, ap, offsets.astype(jnp.float32).reshape(-1))
    return idx[:b, 0], sim[:b, 0]


def imc_cycles_for(am_planes_t_shape: tuple, tile_rows: int = 128,
                   tile_cols: int = 128) -> int:
    """ceil(D/Ar) * ceil(C/Ac) grid steps per batch tile — multi-level
    cells hold the full code, so the cycle count matches the 1-bit
    ``am_search_imc`` grid for the same logical (D, C) geometry."""
    _, dp, c = am_planes_t_shape
    return (-(-dp * 8 // tile_rows)) * (-(-c // tile_cols))
