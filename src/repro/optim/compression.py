"""Int8 error-feedback gradient compression for cross-pod reduction.

At 512+ chips the pod-to-pod (DCI) links are the thinnest pipe in the
data-parallel all-reduce. The classic remedy — int8 quantization with
*error feedback* (the quantization residual is added back into the next
step's gradient) — preserves convergence (Karimireddy et al., 2019) while
cutting cross-pod bytes 4x vs fp32 / 2x vs bf16.

This module provides the quantize/dequantize pair plus a shard_map ring
reduce-scatter/all-gather that moves int8 payloads over a named mesh axis
with ``jax.lax.ppermute``. ``repro/distributed/collectives.py`` wires it
into the train step when ``TrainConfig.grad_compression == "int8_ef"``.

Quantization: per-block (1024) symmetric max-scaling into int8.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
PyTree = Any

_BLOCK = 1024


def ef_int8_compress(g: Array, err: Array) -> Tuple[Array, Array, Array]:
    """Quantize (g + err) to int8 blocks; return (q, scale, new_err).

    g, err: same shape, float. new_err is the residual to carry.
    """
    x = g.astype(jnp.float32) + err.astype(jnp.float32)
    flat = x.reshape(-1)
    pad = -flat.shape[0] % _BLOCK
    fp = jnp.pad(flat, (0, pad))
    blocks = fp.reshape(-1, _BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    deq = (q.astype(jnp.float32) * scale).reshape(-1)[: flat.shape[0]]
    new_err = (flat - deq).reshape(g.shape)
    return q, scale.astype(jnp.float32), new_err


def ef_int8_decompress(q: Array, scale: Array, shape, size: int) -> Array:
    deq = (q.astype(jnp.float32) * scale).reshape(-1)[:size]
    return deq.reshape(shape)


def _requantize(buf: Array) -> Tuple[Array, Array]:
    """Symmetric int8 wire format for a (chunk, _BLOCK) partial sum."""
    s = jnp.max(jnp.abs(buf), axis=-1, keepdims=True) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(buf / s), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def ring_reduce_scatter_int8(deq: Array, axis_name: str) -> Array:
    """Ring reduce-scatter over ``axis_name`` with int8 wire format.

    Standard n-1-hop ring: at hop t, member j sends its running partial
    for chunk (j - t) mod n and folds the incoming partial into its local
    copy of chunk (j - t - 1) mod n. Every hop's payload is re-quantized
    to int8 (+ fp32 per-block scales, 0.4 % overhead) — wire bytes are
    1/4 of an fp32 ring. Error feedback for the *initial* quantization
    happens upstream (``ef_int8_compress``); re-quantization noise along
    the ring is bounded by the per-hop block scaling.

    Args:
      deq: (nblocks, _BLOCK) fp32 shard-local gradient blocks; nblocks
        must be divisible by the axis size.
      axis_name: mesh axis to reduce over.

    Returns:
      (nblocks/n, _BLOCK) fp32 — this member's fully-reduced chunk
      ((me + 1) mod n in chunk order).
    """
    n = jax.lax.axis_size(axis_name)  # static: mesh sizes are known
    me = jax.lax.axis_index(axis_name)
    nb = deq.shape[0]
    if nb % n:
        raise ValueError(f"nblocks={nb} not divisible by axis size {n}")
    chunk = nb // n
    chunks = deq.reshape(n, chunk, _BLOCK)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def local(idx):
        return jax.lax.dynamic_slice_in_dim(chunks, idx % n, 1, axis=0)[0]

    buf = local(me)  # hop 0 sends my own copy of chunk `me`
    for t in range(n - 1):  # unrolled: n is a small static mesh dim
        qw, s = _requantize(buf)
        qr = jax.lax.ppermute(qw, axis_name, perm)
        sr = jax.lax.ppermute(s, axis_name, perm)
        incoming = qr.astype(jnp.float32) * sr
        buf = incoming + local(me - t - 1)
    return buf  # fully reduced chunk (me + 1) mod n


def ring_all_gather(x: Array, axis_name: str) -> Array:
    """Ring all-gather of per-member chunks back to the full array.

    Inverse companion of ``ring_reduce_scatter_int8``: member j enters
    holding chunk (j + 1) mod n and leaves holding all n chunks in order,
    concatenated along axis 0. Payload stays fp32 (the reduced gradient
    must be exact); the *reduce* leg is where compression pays.
    """
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    out = jnp.zeros((n,) + x.shape, x.dtype)
    cur = x
    idx = (me + 1) % n
    out = jax.lax.dynamic_update_slice_in_dim(out, cur[None], idx, axis=0)
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        idx = (idx - 1) % n
        out = jax.lax.dynamic_update_slice_in_dim(
            out, cur[None], idx, axis=0)
    return out.reshape((n * x.shape[0],) + x.shape[1:])
