"""Fused associative-search Pallas kernel: MVM + running arg-max.

The deployment hot loop of the paper (§III-D): similarity of a query batch
against the (D x C) multi-centroid AM followed by arg-max. On the IMC
array this is one analog MVM + a winner-take-all; on TPU we fuse the
arg-max into the MVM's epilogue so similarities never round-trip to HBM:

    grid = (B/bB, C/128, D/128)    # D innermost: similarity accumulation
    scratch: acc (bB x 128) VMEM   — partial sims of the current C block
             best_sim / best_idx   — running winner across C blocks

One (C, D) grid step == one IMC array cycle (asserted against
``repro.core.imc`` in tests), and for the paper's flagship 128x128 AM the
whole search is a single step — the "one-shot associative search" claim,
literally.

C and D may be ragged: padded columns are masked to -inf before the winner
update so they can never win; padded D rows contribute zeros (query and AM
are zero-padded). Ties resolve first-wins, matching ``jnp.argmax``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.deploy.padding import pad_tiles

Array = jax.Array

TILE = 128


def first_argmax(x: Array) -> Array:
    """Row-wise argmax of a (bB, N) block, ties to the LOWEST index.

    ``jnp.argmax``'s rule spelled out as a max and a min: the Mosaic
    lowering of ``argmax`` does not promise which of equal maxima it
    returns, and integer-valued similarities tie often.
    """
    m = jnp.max(x, axis=1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.min(jnp.where(x == m, col, x.shape[1]), axis=1)


def _make_kernel(n_valid_cols: int):
    """Bind the static valid-column count into the kernel body."""

    def kernel(q_ref, am_ref, idx_ref, sim_ref,
               acc_ref, best_sim_ref, best_idx_ref):
        c, d = pl.program_id(1), pl.program_id(2)
        nc, nd = pl.num_programs(1), pl.num_programs(2)

        @pl.when(d == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            q_ref[...].astype(jnp.float32),
            am_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

        @pl.when(d == nd - 1)
        def _fold_winner():
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


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def am_search(q: Array, am_t: Array, *, block_b: int = 256,
              interpret: bool | None = None) -> tuple[Array, Array]:
    """Fused associative search over the multi-centroid AM.

    Args:
      q: (B, D) query hypervectors.
      am_t: (D, C) transposed AM (column c = centroid c), bipolar.
      block_b: query-batch tile height.
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (best_idx, best_sim): (B,) int32 winning centroid per query and
      (B,) float32 its dot similarity.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dd = q.shape
    dd2, c = am_t.shape
    assert dd == dd2, (q.shape, am_t.shape)

    bb = min(block_b, max(b, 1))
    qp = pad_tiles(q.astype(jnp.float32), bb, TILE)
    ap = pad_tiles(am_t.astype(jnp.float32), TILE, TILE)
    gb = qp.shape[0] // bb
    gc = ap.shape[1] // TILE
    gd = qp.shape[1] // TILE

    idx, sim = pl.pallas_call(
        _make_kernel(c),
        grid=(gb, gc, gd),
        in_specs=[
            pl.BlockSpec((bb, TILE), lambda i, cc, d: (i, d)),
            pl.BlockSpec((TILE, TILE), lambda i, cc, d: (d, cc)),
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
        name="am_search",
        interpret=interpret,
    )(qp, ap)
    return idx[:b, 0], sim[:b, 0]


def imc_cycles_for(am_t_shape: tuple) -> int:
    """(C/128)*(D/128) grid steps per batch tile — must equal
    ``repro.core.imc.map_memhd(D, C).cycles``."""
    d, c = am_t_shape
    return (-(-d // TILE)) * (-(-c // TILE))
