"""Device-fidelity associative search: tiled analog MVM + per-tile ADC.

``am_search.py`` computes the deployment search exactly — the digital
semantics. A real IMC deployment computes the same search through
physics: the (D x C) AM is sliced into (A x A) physical arrays, each
array produces an *analog* partial sum for its slice, that current is
digitized by a finite-resolution ADC, and only the digitized per-tile
outputs are accumulated and compared. This kernel executes exactly that
pipeline, so the fidelity knobs of ``ImcSimConfig`` become executable
hardware semantics instead of closed-form accounting:

    grid = (B/bB, C/Ac, D/Ar)        # one (C, D) step == ONE physical
                                     # array pass == one IMC cycle
    per step:  part = q_tile @ am_tile          # analog MVM of one array
               part += offset[d, c]             # per-tile readout drift
               part  = ADC(part)                # clip + mid-tread round
               acc  += part                     # digital accumulation
    at d == nd-1: same running-winner argmax epilogue as am_search.py

The grid is the cost model made literal: ``math.prod(grid[1:]) ==
repro.core.imc.map_memhd(D, C, arr).cycles`` (asserted in
tests/test_imcsim.py), and for the paper's flagship 128x128 AM on a
128x128 array the whole search is one step — the one-shot claim, now
with device physics inside the step.

ADC semantics (shared verbatim with ``ref.adc_quantize``): symmetric
mid-tread quantizer, 2^bits + 1 codes over [-clip, +clip], step =
2*clip / 2^bits, jnp.round tie-to-even. With the default power-of-two
clip (the array row count), bipolar partial sums are integers and the
step is a power of two, so any ``adc_bits`` with step <= 1 (b >= 8 at
A=128; b >= 16 trivially) reproduces the exact digital search bit for
bit — similarities AND first-wins tie-breaks. That is the
fidelity-parity contract.

Conductance noise and stuck-at faults are *storage* perturbations: they
are applied to the resident AM before it reaches this kernel (see
``repro.imcsim.device``); the kernel models the readout path (tiling,
drift offsets, ADC).

Non-default array geometries (``arr.rows``/``arr.cols`` not multiples
of the TPU 128-lane tile) are simulation-only territory: they run in
interpret mode, which is where the robustness sweeps live anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.deploy.padding import pad_tiles
from repro.kernels.am_search import first_argmax

Array = jax.Array


def _make_kernel(n_valid_cols: int, adc_bits: int, adc_clip: float,
                 tile_cols: int):
    """Bind static valid-column count + ADC transfer into the body."""
    step = 2.0 * adc_clip / (2 ** adc_bits)

    def kernel(q_ref, am_ref, off_ref, idx_ref, sim_ref,
               acc_ref, best_sim_ref, best_idx_ref):
        c, d = pl.program_id(1), pl.program_id(2)
        nc, nd = pl.num_programs(1), pl.num_programs(2)

        @pl.when(d == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # One physical array's analog MVM pass...
        part = jnp.dot(
            q_ref[...].astype(jnp.float32),
            am_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        # ...its readout offset, and its ADC. Digital accumulation only
        # ever sees the quantized tile outputs.
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
    "tile_rows", "tile_cols", "adc_bits", "adc_clip", "block_b",
    "interpret"))
def am_search_imc(q: Array, am_t: Array, offsets: Array | None = None, *,
                  tile_rows: int = 128, tile_cols: int = 128,
                  adc_bits: int = 16, adc_clip: float = 128.0,
                  block_b: int = 256, interpret: bool | None = None,
                  ) -> tuple[Array, Array]:
    """Associative search as the tiled analog arrays would compute it.

    Args:
      q: (B, D) query hypervectors.
      am_t: (D, C) transposed resident AM — typically the *perturbed*
        bipolar AM from ``repro.imcsim.device.perturb_am``.
      offsets: (ceil(D/tile_rows), ceil(C/tile_cols)) per-tile readout
        offsets, or None for drift-free readout.
      tile_rows / tile_cols: physical array geometry (ImcArrayConfig).
      adc_bits / adc_clip: ADC resolution and full-scale range.
      block_b: query-batch tile height.
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (best_idx, best_sim): (B,) int32 winning centroid per query and
      (B,) float32 its ADC-quantized accumulated similarity.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dd = q.shape
    dd2, c = am_t.shape
    assert dd == dd2, (q.shape, am_t.shape)

    bb = min(block_b, max(b, 1))
    qp = pad_tiles(q.astype(jnp.float32), bb, tile_rows)
    ap = pad_tiles(am_t.astype(jnp.float32), tile_rows, tile_cols)
    gb = qp.shape[0] // bb
    gc = ap.shape[1] // tile_cols
    gd = qp.shape[1] // tile_rows
    if offsets is None:
        offsets = jnp.zeros((gd, gc), jnp.float32)
    if offsets.shape != (gd, gc):
        raise ValueError(
            f"offsets shape {offsets.shape} != tile grid {(gd, gc)}")

    idx, sim = pl.pallas_call(
        _make_kernel(c, adc_bits, float(adc_clip), tile_cols),
        grid=(gb, gc, gd),
        in_specs=[
            pl.BlockSpec((bb, tile_rows), lambda i, cc, d: (i, d)),
            pl.BlockSpec((tile_rows, tile_cols), lambda i, cc, d: (d, cc)),
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
        name="am_search_imc",
        interpret=interpret,
    )(qp, ap, offsets.astype(jnp.float32).reshape(-1))
    return idx[:b, 0], sim[:b, 0]


def imc_cycles_for(am_t_shape: tuple, tile_rows: int = 128,
                   tile_cols: int = 128) -> int:
    """ceil(D/Ar) * ceil(C/Ac) grid steps per batch tile — must equal
    ``repro.core.imc.map_memhd(D, C, arr).cycles`` for the matching
    array geometry (the hardware-model == kernel-geometry contract)."""
    d, c = am_t_shape
    return (-(-d // tile_rows)) * (-(-c // tile_cols))
