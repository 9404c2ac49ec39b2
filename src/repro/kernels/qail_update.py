"""Fused QAIL inner-step Pallas kernel: sims MVM + Eq.-(4)/(5) + Eq.-(6).

The training hot loop of the paper (§III-C): each minibatch computes the
similarity of its binarized queries against the binary AM, selects the
push-away (Eq. 4, global argmax) and pull-toward (Eq. 5, true-class
argmax) centroids for every mispredicted sample, and emits the Eq.-(6)
delta for the float shadow AM. Unfused, that is a matmul, two argmax
reductions, two gathers, and a scatter — five HBM round-trips of (B, C)
similarities and (C, D) deltas per batch.

Here the whole step is ONE VMEM-resident pass: the grid walks query
blocks only, with the transposed binary AM, the update payload and the
(C, D) delta accumulator resident in VMEM across steps. Scatter-free by
construction — target selection becomes a one-hot selection matrix W
(B, C) with W[i] = lr*mis_i*(onehot(true) - onehot(pred)), and the delta
is the MXU matmul W^T @ upd accumulated over query blocks, contracted at
float32 precision (the payload is float; the TPU default would round it
to bf16). The miss count rides along in a lane-wide (1, 128)
accumulator (Mosaic stores no scalars to VMEM), so training needs no
second pass to know its error rate.

Padded columns are masked to -inf before both argmaxes (they can never
be selected); padded rows carry mask 0 and label -1 (their W row is
zero); padded D columns contribute zero delta. Ties resolve first-wins,
matching ``jnp.argmax`` and ``kernels.ref.qail_update_delta``, the
bit-exact oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.deploy.padding import pad_tiles, pad_vec
from repro.kernels.am_search import first_argmax

Array = jax.Array

TILE = 128

# Batch-tile height of the query-block grid walk: the free tiling knob
# (the AM, payload and (C, D) delta stay VMEM-resident regardless).
# ``kernels.autotune`` searches TUNE_BLOCK_B per geometry and ops.py
# applies the cached winner; DEFAULT_BLOCK_B is the fallback.
DEFAULT_BLOCK_B = 256
TUNE_BLOCK_B = (64, 128, 256, 512, 1024)


def _vmem_limit(bb: int, d: int, c: int) -> int:
    """Scoped VMEM for one call: the double-buffered blocks (queries,
    payload, resident AM, (C, D) delta), the delta temporary and the
    (bB, C) selection intermediates, in float32 words, doubled for
    headroom. The AM and delta alone exceed the 16 MiB default at
    1024x1024; a v5e core has 128 MiB."""
    words = 2 * (2 * bb * d + 2 * d * c) + d * c + 8 * bb * c
    return min(max(2 * 4 * words, 32 << 20), 100 << 20)


def _make_kernel(n_valid_cols: int, lr: float):
    """Bind the static valid-column count and learning rate."""

    def kernel(q_ref, upd_ref, am_ref, own_ref, y_ref, mask_ref,
               delta_ref, miss_ref):
        b, nb = pl.program_id(0), pl.num_programs(0)

        @pl.when(b == 0)
        def _init():
            delta_ref[...] = jnp.zeros_like(delta_ref)
            miss_ref[...] = jnp.zeros_like(miss_ref)

        sims = jnp.dot(q_ref[...].astype(jnp.float32),
                       am_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)  # (bB, C)
        col = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
        valid = col < n_valid_cols
        neg = jnp.finfo(jnp.float32).min
        sims = jnp.where(valid, sims, neg)

        owners = own_ref[...]          # (1, C) int32, padded cols = -1
        labels = y_ref[...]            # (bB, 1) int32, padded rows = -1

        # Eq. (4): global argmax -> push-away target, one-hot on C.
        pred_t = first_argmax(sims)  # (bB,)
        pred_hot = col == pred_t[:, None]  # (bB, C)
        pred_class = jnp.sum(jnp.where(pred_hot, owners, 0), axis=1,
                             keepdims=True)  # (bB, 1)

        # Eq. (5): argmax within the true class -> pull-toward target.
        own_mask = (owners == labels) & valid  # (bB, C)
        true_t = first_argmax(jnp.where(own_mask, sims, neg))
        true_hot = col == true_t[:, None]

        mis = ((pred_class != labels).astype(jnp.float32)
               * mask_ref[...])  # (bB, 1)

        # Eq. (6) as a selection matmul: delta += W^T @ upd on the MXU.
        w = (lr * mis) * (true_hot.astype(jnp.float32)
                          - pred_hot.astype(jnp.float32))
        delta_ref[...] += jnp.dot(w.T, upd_ref[...].astype(jnp.float32),
                                  preferred_element_type=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST)
        miss_ref[...] += jnp.sum(mis, axis=0, keepdims=True)
        del nb

    return kernel


@functools.partial(jax.jit, static_argnames=("lr", "block_b", "interpret"))
def qail_update(q: Array, upd: Array, am_t: Array, centroid_class: Array,
                labels: Array, mask: Array, *, lr: float,
                block_b: int = DEFAULT_BLOCK_B,
                interpret: bool | None = None) -> tuple[Array, Array]:
    """Fused QAIL inner step for one minibatch.

    Args:
      q: (B, D) binarized queries H^b.
      upd: (B, D) Eq.-(6) update payload (encoded H or H^b).
      am_t: (D, C) transposed binary AM (column c = centroid c).
      centroid_class: (C,) int centroid ownership.
      labels: (B,) int true labels (-1 marks padded rows).
      mask: (B,) float {0, 1} sample validity.
      lr: iterative-learning rate alpha (static).
      block_b: query-block tile height (grid walks B only; AM, payload
        and the (C, D) delta stay VMEM-resident across blocks).
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (delta, n_miss): (C, D) float32 Eq.-(6) AM increment and the
      scalar float32 count of mispredicted (masked) samples. Bit-exact
      vs ``kernels.ref.qail_update_delta``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, dd = q.shape
    dd2, c = am_t.shape
    assert dd == dd2, (q.shape, am_t.shape)
    assert upd.shape == q.shape, (upd.shape, q.shape)

    bb = min(block_b, max(b, 1))
    qp = pad_tiles(q.astype(jnp.float32), bb, TILE)
    up = pad_tiles(upd.astype(jnp.float32), bb, TILE)
    ap = pad_tiles(am_t.astype(jnp.float32), TILE, TILE)
    pb, pd = qp.shape[0] - b, qp.shape[1] - dd
    pc = ap.shape[1] - c
    ownp = pad_vec(centroid_class.astype(jnp.int32), c + pc,
                   value=-1)[None, :]
    yp = pad_vec(labels.astype(jnp.int32), b + pb, value=-1)[:, None]
    mp = pad_vec(mask.astype(jnp.float32), b + pb)[:, None]
    gb = qp.shape[0] // bb

    delta, miss = pl.pallas_call(
        _make_kernel(c, lr),
        grid=(gb,),
        in_specs=[
            pl.BlockSpec((bb, dd + pd), lambda i: (i, 0)),
            pl.BlockSpec((bb, dd + pd), lambda i: (i, 0)),
            pl.BlockSpec((dd + pd, c + pc), lambda i: (0, 0)),
            pl.BlockSpec((1, c + pc), lambda i: (0, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((c + pc, dd + pd), lambda i: (0, 0)),
            pl.BlockSpec((1, TILE), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c + pc, dd + pd), jnp.float32),
            jax.ShapeDtypeStruct((1, TILE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(bb, dd + pd, c + pc)),
        name="qail_update",
        interpret=interpret,
    )(qp, up, ap, ownp, yp, mp)
    return delta[:c, :dd], miss[0, 0]
