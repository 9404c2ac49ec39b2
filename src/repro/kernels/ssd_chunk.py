"""Pallas SSD chunk kernel — the Mamba-2 hot loop, one chunk per pass.

The fused-scan SSD (models/layers.py::ssd_forward) is the dominant cost
of the mamba2/hymba cells; its per-chunk body is a natural TPU kernel:
everything for one (chunk Q, head) pair — the (Q, Q) decay matrix, the
intra-chunk attention-like product, the inter-chunk state contribution,
and the state update — lives comfortably in VMEM, and the (Q,Q)@(Q,P)
and (Q,N)@(N,P) contractions are MXU work.

    grid = (B, H)          # one (batch row, head) per pass
    in:  x (Q,P), b/c (Q,N), dt/da (Q,), state (N,P)
    out: y (Q,P), new_state (N,P)

The chunk-to-chunk dependency (state) stays in the caller's scan —
kernels keep the per-chunk math, the framework keeps the recurrence.
``ref_ssd_chunk`` is the pure-jnp oracle (mirrors ssd_forward's body).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def ref_ssd_chunk(x: Array, b: Array, c: Array, dt: Array, da: Array,
                  state: Array):
    """Oracle. x: (B,Q,H,P), b/c: (B,Q,H,N), dt/da: (B,Q,H),
    state: (B,H,N,P) -> (y (B,Q,H,P), new_state (B,H,N,P))."""
    q = x.shape[1]
    cum = jnp.cumsum(da, axis=1)                       # (B,Q,H)
    seg_total = cum[:, -1]                             # (B,H)
    xdt = x.astype(jnp.float32) * dt[..., None]
    b32 = b.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    mask = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    decay = jnp.where(mask,
                      jnp.exp(cum[:, :, None, :] - cum[:, None, :, :]), 0.0)
    cb = jnp.einsum("bqhn,bkhn->bqkh", c32, b32)
    y_intra = jnp.einsum("bqkh,bkhp->bqhp", cb * decay, xdt)
    in_decay = jnp.exp(cum)
    y_inter = jnp.einsum("bqhn,bhnp->bqhp", c32 * in_decay[..., None],
                         state.astype(jnp.float32))
    state_decay = jnp.exp(seg_total[:, None, :] - cum)
    bx = jnp.einsum("bqhn,bqhp->bhnp", b32 * state_decay[..., None], xdt)
    new_state = state.astype(jnp.float32) \
        * jnp.exp(seg_total)[..., None, None] + bx
    return (y_intra + y_inter).astype(x.dtype), new_state


def _kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, s_ref,
            y_ref, snew_ref):
    x = x_ref[0, :, 0, :].astype(jnp.float32)     # (Q, P)
    b = b_ref[0, :, 0, :].astype(jnp.float32)     # (Q, N)
    c = c_ref[0, :, 0, :].astype(jnp.float32)     # (Q, N)
    dt = dt_ref[0, :, 0].astype(jnp.float32)      # (Q,)
    da = da_ref[0, :, 0].astype(jnp.float32)      # (Q,)
    s = s_ref[0, 0].astype(jnp.float32)           # (N, P)
    q = x.shape[0]

    cum = jnp.cumsum(da)                          # (Q,)
    seg_total = cum[-1]
    xdt = x * dt[:, None]

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.where(row >= col, jnp.exp(cum[:, None] - cum[None, :]),
                      0.0)                         # (Q, Q)
    cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)  # (Q, Q)
    y_intra = jnp.dot(cb * decay, xdt,
                      preferred_element_type=jnp.float32)     # (Q, P)
    y_inter = jnp.dot(c * jnp.exp(cum)[:, None], s,
                      preferred_element_type=jnp.float32)     # (Q, P)
    bx = jnp.dot((b * jnp.exp(seg_total - cum)[:, None]).T, xdt,
                 preferred_element_type=jnp.float32)          # (N, P)
    s_new = s * jnp.exp(seg_total) + bx

    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)
    snew_ref[0, 0] = s_new.astype(snew_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x: Array, b: Array, c: Array, dt: Array, da: Array,
              state: Array, *, interpret: bool | None = None):
    """One SSD chunk for all (batch, head) pairs.

    Shapes as in ``ref_ssd_chunk``. Returns (y, new_state).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, q, h, p = x.shape
    n = b.shape[-1]

    y, s_new = pl.pallas_call(
        _kernel,
        grid=(bsz, h),
        in_specs=[
            pl.BlockSpec((1, q, 1, p), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, q, 1, n), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, q, 1, n), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, q, 1), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, q, 1), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, 1, p), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        name="ssd_chunk",
        interpret=interpret,
    )(x, b, c, dt, da, state)
    return y, s_new
