"""1-bit pack/unpack Pallas kernels — binary AM storage.

The paper's memory-efficiency claims (Table I, Fig. 3) count the AM and
projection matrix at 1 bit per cell. These kernels realize that storage
format on TPU: bipolar (+-1) tiles are packed 8 cells/byte (LSB-first)
for HBM residence and unpacked tile-by-tile into VMEM for compute.

Blocks are (block_r, 1024) cells <-> (block_r, 128) bytes, so both sides
are lane-aligned. Mosaic cannot reshape a lane axis into (bytes, 8), so
the byte <-> bit regrouping runs on the MXU instead: packing multiplies
the {0, 1} cells by the (L, L/8) byte-weight matrix (``pack_lanes``),
unpacking replicates each byte over its 8 lanes with a 0/1 spread matrix
and shifts out one bit per lane. Both are exact: every operand is 0, 1
or a power of two (bf16-exact) and every sum is at most 255.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.deploy.padding import pad_tiles

Array = jax.Array

LANES = 1024  # unpacked cells per block column; packed cols = LANES // 8


def _byte_of(shape: tuple, cell_axis: int) -> tuple[Array, Array]:
    """(cell, byte) membership and bit weight over a cells x bytes grid;
    ``cell_axis`` says which axis of ``shape`` indexes cells."""
    cell = jax.lax.broadcasted_iota(jnp.int32, shape, cell_axis)
    byte = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - cell_axis)
    return (cell >> 3) == byte, jnp.left_shift(1, cell & 7)


def pack_lanes(bits: Array) -> Array:
    """(R, L) {0, 1} cells -> (R, L // 8) uint8, LSB-first, on the MXU.

    Kernel-side packer shared with ``encode_fused``: one bf16 matmul
    against the byte-weight matrix, exact (sums of distinct powers of
    two below 256).
    """
    n = bits.shape[1]
    member, weight = _byte_of((n, n // 8), 0)
    w = jnp.where(member, weight, 0).astype(jnp.bfloat16)
    packed = jnp.dot(bits.astype(jnp.bfloat16), w,
                     preferred_element_type=jnp.float32)
    return packed.astype(jnp.int32).astype(jnp.uint8)


def _pack_kernel(x_ref, o_ref):
    o_ref[...] = pack_lanes((x_ref[...] > 0).astype(jnp.float32))


def _unpack_kernel(p_ref, o_ref):
    member, _ = _byte_of((LANES // 8, LANES), 1)
    spread = member.astype(jnp.bfloat16)  # byte j -> its 8 lanes
    p = p_ref[...].astype(jnp.int32).astype(jnp.bfloat16)
    byte = jnp.dot(p, spread,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, byte.shape, 1)
    bit = (byte >> (lane & 7)) & 1
    o_ref[...] = bit.astype(jnp.float32) * 2 - 1


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def pack_bits(x: Array, *, block_r: int = 256,
              interpret: bool | None = None) -> Array:
    """(R, C) bipolar -> (R, C // 8) uint8, C % 8 == 0 (pad upstream)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r, c = x.shape
    if c % 8:
        raise ValueError(f"C={c} must be a multiple of 8")
    br = min(block_r, max(r, 1))
    xp = pad_tiles(x.astype(jnp.float32), br, LANES, value=-1.0)
    gr, gc = xp.shape[0] // br, xp.shape[1] // LANES

    out = pl.pallas_call(
        _pack_kernel,
        grid=(gr, gc),
        in_specs=[pl.BlockSpec((br, LANES), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, LANES // 8), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], xp.shape[1] // 8),
                                       jnp.uint8),
        name="pack_bits",
        interpret=interpret,
    )(xp)
    return out[:r, : c // 8]


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def unpack_bits(packed: Array, *, block_r: int = 256,
                interpret: bool | None = None) -> Array:
    """(R, C//8) uint8 -> (R, C) bipolar float32 {-1, +1}."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r, cb = packed.shape
    br = min(block_r, max(r, 1))
    pp = pad_tiles(packed, br, LANES // 8)
    gr, gc = pp.shape[0] // br, pp.shape[1] // (LANES // 8)

    out = pl.pallas_call(
        _unpack_kernel,
        grid=(gr, gc),
        in_specs=[pl.BlockSpec((br, LANES // 8), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pp.shape[0], pp.shape[1] * 8),
                                       jnp.float32),
        name="unpack_bits",
        interpret=interpret,
    )(pp)
    return out[:r, : cb * 8]
