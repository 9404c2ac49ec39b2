"""Tiled bipolar MVM Pallas kernel — the TPU realization of an IMC array.

The paper's encoder (and every IMC mapping it compares against) is a
matrix-vector multiply streamed through 128x128 crossbar tiles. The MXU is
*also* a 128x128 systolic tile, so the natural TPU adaptation is a Pallas
kernel whose BlockSpec grid reproduces the IMC tiling exactly:

    grid = (B/bB, N/128, K/128)       # K innermost: accumulation
    one grid step == one array "cycle" of the paper's cost model
      (asserted against repro.core.imc in tests/test_kernels.py)

VMEM working set per step: bB*128 (x tile) + 128*128 (w tile) + bB*128
(accumulator) floats — comfortably inside the ~16 MB/core VMEM for
bB <= 512.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.deploy.padding import pad_tiles

Array = jax.Array

TILE = 128  # IMC array dim == MXU tile dim


def _mvm_kernel(x_ref, w_ref, o_ref):
    """One (bB, bK) x (bK, bN) tile pass with K-accumulation in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def binary_mvm(x: Array, w: Array, *, block_b: int = 128,
               interpret: bool | None = None) -> Array:
    """H = x @ w via 128x128 IMC-geometry tiles.

    Args:
      x: (B, K) float input (features / queries).
      w: (K, N) bipolar weights (projection matrix or AM).
      block_b: batch tile height.
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (B, N) float32.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)

    bb = min(block_b, max(b, 1))
    xp = pad_tiles(x.astype(jnp.float32), bb, TILE)
    wp = pad_tiles(w.astype(jnp.float32), TILE, TILE)
    gb, gk, gn = (xp.shape[0] // bb, xp.shape[1] // TILE,
                  wp.shape[1] // TILE)

    out = pl.pallas_call(
        _mvm_kernel,
        grid=(gb, gn, gk),
        in_specs=[
            pl.BlockSpec((bb, TILE), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((TILE, TILE), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bb, TILE), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]),
                                       jnp.float32),
        name="binary_mvm",
        interpret=interpret,
    )(xp, wp)
    return out[:b, :n]


def imc_cycles_for(x_shape: tuple, w_shape: tuple) -> int:
    """Grid size of the K x N tiling — equals the IMC cycle count of
    ``repro.core.imc.map_basic(K, N)`` (batch tiles reuse resident
    weights, so the per-sample cycle count ignores the batch axis)."""
    k, n = w_shape
    return (-(-k // TILE)) * (-(-n // TILE))
