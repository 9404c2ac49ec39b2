"""Coarse pass of the hierarchical AM search: top-S cluster shortlist.

The flat packed scan (``am_search_packed``) is linear in centroid count C;
at C in the 10^5+ regime (per-user / per-entity label spaces) that is the
wrong algorithm. The hierarchical subsystem splits the query into

  1. this kernel — score the query against G packed *super-centroids*
     (one per kmeans cluster of the trained AM) and keep the S best
     clusters per query, and
  2. ``am_search_sparse`` — search only the packed tiles belonging to
     those S clusters, with a streaming top-k epilogue.

The Hamming accumulation is byte-for-byte the ``am_search_packed``
popcount path (XOR + 3-step SWAR on the VPU, same (bB, 128-col, packed-D
block) grid); the epilogue differs: instead of one running argmax the
kernel keeps a per-query streaming top-S scratch, merged block-by-block
with an iterated select-max-then-min-id reduction so results are ordered
by (-similarity, cluster id) — ties resolve toward the LOWER cluster id,
matching the stable argsort oracle ``ref.am_shortlist`` exactly.

Similarities are integer-valued (exact in float32), so the top-S set and
its order are bit-exact with the oracle, which is what lets the S = G
degenerate configuration of the full two-stage pipeline reproduce the
flat scan bit-for-bit.
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

Array = jax.Array

DEFAULT_BLOCK_B = 256
TUNE_BLOCK_B = (64, 128, 256, 512, 1024)

_NEG = float(jnp.finfo(jnp.float32).min)
_SENT = int(jnp.iinfo(jnp.int32).max)


def topk_select(sims: Array, ids: Array, k: int) -> tuple[Array, Array]:
    """Row-wise top-k of (sims, ids) pairs ordered by (-sim, id).

    sims: (B, N) float32, ids: (B, N) int32. Returns ((B, k) sims,
    (B, k) ids), best first. k is a static Python int — the selection is
    k unrolled max-then-min-id steps, which keeps the epilogue fusable
    inside a Pallas kernel body (no sort primitive needed) and encodes
    the tie-break exactly: among equal similarities the LOWEST id wins.
    Exhausted slots decay to (float32-min, int32-max) sentinels.

    Composite float/int sort keys are deliberately avoided: an int32
    (sim, id) pack overflows once D * C grows past 2^31 and float keys
    lose id bits to the mantissa; the iterated select is exact at any
    geometry.
    """
    out_s, out_i = [], []
    for _ in range(k):
        m = jnp.max(sims, axis=1, keepdims=True)  # (B, 1)
        pick = jnp.min(jnp.where(sims == m, ids, _SENT), axis=1,
                       keepdims=True)
        out_s.append(m)
        out_i.append(pick)
        drop = (sims == m) & (ids == pick)
        sims = jnp.where(drop, _NEG, sims)
        ids = jnp.where(drop, _SENT, ids)
    return jnp.concatenate(out_s, axis=1), jnp.concatenate(out_i, axis=1)


def _make_kernel(n_valid_cols: int, n_valid_dims: int, s: int):
    def kernel(q_ref, am_ref, idx_ref, sim_ref,
               acc_ref, best_sim_ref, best_idx_ref):
        c, d = pl.program_id(1), pl.program_id(2)
        nc, nd = pl.num_programs(1), pl.num_programs(2)

        @pl.when(d == 0)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a = am_ref[...].astype(jnp.int32)  # (P, TILE)
        accumulate_hamming(q_ref, acc_ref, lambda rows: a[None])

        @pl.when(d == nd - 1)
        def _fold_topk():
            sims = n_valid_dims - 2.0 * acc_ref[...]  # (bB, TILE)
            col = c * TILE + jax.lax.broadcasted_iota(
                jnp.int32, sims.shape, 1)
            valid = col < n_valid_cols
            sims = jnp.where(valid, sims, _NEG)
            ids = jnp.where(valid, col, _SENT)
            blk_s, blk_i = topk_select(sims, ids, s)

            @pl.when(c == 0)
            def _first():
                best_sim_ref[...] = blk_s
                best_idx_ref[...] = blk_i

            @pl.when(c > 0)
            def _merge():
                ms, mi = topk_select(
                    jnp.concatenate([best_sim_ref[...], blk_s], axis=1),
                    jnp.concatenate([best_idx_ref[...], blk_i], axis=1),
                    s)
                best_sim_ref[...] = ms
                best_idx_ref[...] = mi

            @pl.when(c == nc - 1)
            def _emit():
                bs = best_sim_ref[...]
                bi = best_idx_ref[...]
                idx_ref[...] = jnp.where(bs > _NEG, bi, -1)
                sim_ref[...] = bs

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "n_dims", "s", "n_cols", "block_b", "interpret"))
def am_shortlist(q_packed: Array, super_packed_t: Array, *,
                 n_dims: int, s: int, n_cols: int | None = None,
                 block_b: int = DEFAULT_BLOCK_B,
                 interpret: bool | None = None) -> tuple[Array, Array]:
    """Score packed queries against G packed super-centroids, keep top S.

    Args:
      q_packed: (B, Dp) uint8 packed queries (``pack_rows``), tail bits 0.
      super_packed_t: (Dp, G) uint8 transposed packed super-centroids —
        ``pack_rows(super_am).T`` for a (G, D) bipolar super-AM.
      n_dims: true hypervector dimension D.
      s: shortlist length, 1 <= s <= G (static).
      n_cols: true cluster count G; defaults to super_packed_t.shape[1].
      block_b: query-batch tile height.
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (cluster_idx, cluster_sims): (B, s) int32 and (B, s) float32,
      best-first, ties toward the lower cluster id — bit-exact with
      ``ref.am_shortlist``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dp = q_packed.shape
    dp2, g = super_packed_t.shape
    assert dp == dp2, (q_packed.shape, super_packed_t.shape)
    if n_cols is None:
        n_cols = g
    if not 1 <= s <= n_cols:
        raise ValueError(f"shortlist s={s} outside [1, {n_cols}]")
    if not dp * 8 >= n_dims > (dp - 1) * 8:
        raise ValueError(f"n_dims={n_dims} inconsistent with Dp={dp}")

    p = dp_block(dp)
    bb = batch_block(block_b, b)
    qp = pad_tiles(q_packed, bb, p)
    ap = pad_tiles(super_packed_t, p, TILE)
    gb = qp.shape[0] // bb
    gc = ap.shape[1] // TILE
    gd = qp.shape[1] // p

    idx, sim = pl.pallas_call(
        _make_kernel(n_cols, n_dims, s),
        grid=(gb, gc, gd),
        in_specs=[
            pl.BlockSpec((bb, p), lambda i, cc, d: (i, d)),
            pl.BlockSpec((p, TILE), lambda i, cc, d: (d, cc)),
        ],
        out_specs=[
            pl.BlockSpec((bb, s), lambda i, cc, d: (i, 0)),
            pl.BlockSpec((bb, s), lambda i, cc, d: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], s), jnp.int32),
            jax.ShapeDtypeStruct((qp.shape[0], s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, TILE), jnp.float32),
            pltpu.VMEM((bb, s), jnp.float32),
            pltpu.VMEM((bb, s), jnp.int32),
        ],
        name="am_shortlist",
        interpret=interpret,
    )(qp, ap)
    return idx[:b], sim[:b]
