"""Grouped matmul over expert-sorted rows: the MoE layer's expert FFNs.

The rows of ``lhs`` are the routed token slots, sorted by expert, and
``group_sizes`` says how many belong to each group. Group g < G (G =
``rhs.shape[0]``, the experts this device holds) multiplies its rows by
``rhs[g]``; groups from G on are not computed, and their rows of the
output are left unwritten. With a second right-hand side the kernel
computes the gated FFN's first half, ``silu(x @ rhs[g]) * (x @ rhs2[g])``,
in one pass over the rows.

    grid = (N / tn, active m tiles, K / tk)      # K innermost

Only the m tiles that hold rows of a computed group are visited: the
grid's middle extent is a device value (the group metadata of
``jax.experimental.pallas.ops.tpu.megablox``, scalar-prefetched), so the
work follows the routed slots and not the buffer's size. A tile that
two groups share is visited once for each, and each visit stores only
its group's rows. bf16 operands multiply on the MXU in one pass with
float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

Array = jax.Array

TM = 256  # rows a tile: about one held expert's load at 8,192 tokens


def _tile(n: int, choices) -> int:
    """The largest of ``choices`` that divides n, else n (one block)."""
    return next((c for c in choices if n % c == 0), n)


def _make_kernel(swiglu: bool, tm: int):
    def kernel(offsets, gids, mtiles, *refs):
        if swiglu:
            lhs_ref, rhs_ref, rhs2_ref, out_ref, acc_ref, acc2_ref = refs
        else:
            lhs_ref, rhs_ref, out_ref, acc_ref = refs
        i, kk = pl.program_id(1), pl.program_id(2)

        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if swiglu:
                acc2_ref[...] = jnp.zeros_like(acc2_ref)

        x = lhs_ref[...]
        acc_ref[...] += jnp.dot(x, rhs_ref[...],
                                preferred_element_type=jnp.float32)
        if swiglu:
            acc2_ref[...] += jnp.dot(x, rhs2_ref[...],
                                     preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(2) - 1)
        def _store():
            g = gids[i]
            row = mtiles[i] * tm + jax.lax.broadcasted_iota(
                jnp.int32, out_ref.shape, 0)
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            val = acc_ref[...]
            if swiglu:
                val = jax.nn.silu(val) * acc2_ref[...]
            out_ref[...] = jnp.where(mine, val, out_ref[...].astype(
                jnp.float32)).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_gmm(lhs: Array, rhs: Array, group_sizes: Array,
            rhs2: Array | None = None, *, tm: int = TM,
            interpret: bool | None = None) -> Array:
    """Grouped matmul of expert-sorted rows.

    Args:
      lhs: (m, K) rows sorted by group; m a multiple of ``tm``.
      rhs: (G, K, N) one matrix per computed group.
      group_sizes: (G2,) int32 rows per group, G2 >= G, summing to m.
      rhs2: optional (G, K, N): then the result is
        ``silu(lhs @ rhs) * (lhs @ rhs2)`` row by row.
      tm: rows a tile.

    Returns:
      (m, N) in ``lhs``'s dtype: the rows of groups < G computed, the
      others unwritten.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, kd = lhs.shape
    g, _, nd = rhs.shape
    if m % tm:
        raise ValueError(f"rows {m} not a multiple of the tile {tm}")
    tk = _tile(kd, (512, 256, 128))
    tn = _tile(nd, (1024, 512, 256, 128))
    (offsets, gids, mtiles), n_tiles = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=g,
        visit_empty_groups=False)
    swiglu = rhs2 is not None
    lhs_spec = pl.BlockSpec((tm, tk), lambda n, i, k, o, gi, mt: (mt[i], k))
    rhs_spec = pl.BlockSpec((None, tk, tn),
                            lambda n, i, k, o, gi, mt: (gi[i], k, n))
    in_specs = [lhs_spec, rhs_spec] + ([rhs_spec] if swiglu else [])
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)] * (2 if swiglu else 1)
    return pl.pallas_call(
        _make_kernel(swiglu, tm),
        out_shape=jax.ShapeDtypeStruct((m, nd), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, i, k, o, gi, mt: (mt[i], n)),
            grid=(nd // tn, n_tiles, kd // tk),
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="moe_gmm",
        interpret=interpret,
    )(offsets, gids, mtiles, lhs, rhs, *([rhs2] if swiglu else []))
