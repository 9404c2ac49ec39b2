"""Fused feature->packed-query encoding: projection MVM + sign + bitpack.

The serving path used to stage the encoder: float einsum H = F @ M,
round-trip the (B, D) float hypervector through HBM, binarize it, pack
it, and only then dispatch the XOR+popcount search. But the only thing
the search ever reads is one *bit* per dimension (sign(H) >= 0), so the
float H is pure HBM traffic. This kernel closes that gap: it tiles the
bipolar projection MVM over 128-row K slabs, keeps the accumulator in
VMEM across them, and on the last K step emits the sign-binarized,
uint8-packed query row directly — no float H ever touches HBM.

    grid = (B/bB, D/TD, f/128)       # f innermost: accumulation
    TD = D padded to 128, at most 1024 dims per block
    out block per (i, j): (bB, TD/8) uint8 — the whole packed row, or
    one lane-aligned 128-byte slab of it

The packed output block must be lane-aligned (a multiple of 128 bytes
or the whole packed axis), so one D block spans up to 1024 dims rather
than one 128x128 array; the IMC cycle count stays a function of shapes
(``imc_cycles_for``). The bitpack epilogue is ``pack_bits.pack_lanes``
(an exact 0/1 MXU matmul; Mosaic cannot reshape lanes into bytes).

Bit semantics are exactly the staged chain's
``encode_query -> pack_rows``: a bit is 1 iff the accumulated H >= 0
(``binarize_query`` maps sign(0) -> +1 and ``pack_bits`` packs +1 as
bit 1), bits are LSB-first along D, and columns >= n_dims (the padded
D tail) pack as 0 so they XOR-cancel against the identically padded AM.
Validated bit-for-bit against ``ref.encode_pack`` in
tests/test_kernel_parity.py.

Parity caveat: for f > 128 the kernel sums the MVM in 128-wide K slabs
while the staged einsum may reduce in a different order, so for
*non-integer* features the two H values can differ by float rounding —
a bit flips only when the true H sits within that rounding error of 0.
Both sides contract at float32 precision (``Precision.HIGHEST``; the
TPU default would round the features to bf16). Bipolar/integer
features are exact (integer accumulation); float features agree for
every tested geometry and seed, but "bit-exact" is a structural
guarantee only where H is integer-valued.

``search_from_features`` / ``predict_from_features`` chain this kernel
straight into ``am_search_packed`` under ONE jit — the whole
feature->prediction pipeline is a single host dispatch with only the
(B, ceil(D/8)) packed rows materialized between the two kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.deploy.padding import pad_tiles, round_up

from repro.kernels.am_search_packed import am_search_packed
from repro.kernels.pack_bits import pack_lanes

Array = jax.Array

TILE = 128          # IMC array dim == MXU tile dim
MAX_TD = 1024       # dims per D block: 128 packed bytes, one lane row

# Batch-tile height: the free tiling knob (TILE is the IMC-geometry /
# MXU contract). ``kernels.autotune`` searches TUNE_BLOCK_B and ops.py
# dispatch applies the cached winner; DEFAULT_BLOCK_B is the fallback.
DEFAULT_BLOCK_B = 128
TUNE_BLOCK_B = (32, 64, 128, 256, 512)


def _make_kernel(n_valid_dims: int, bf16: bool):
    """Bind the static valid-dimension count into the kernel body.

    float32 features contract at ``HIGHEST``; bfloat16 features against
    the bipolar projection (exact in bfloat16) take one MXU pass with
    float32 accumulation, every product exact."""

    def kernel(x_ref, w_ref, o_ref, acc_ref):
        j, k = pl.program_id(1), pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if bf16:
            acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                    preferred_element_type=jnp.float32)
        else:
            acc_ref[...] += jnp.dot(
                x_ref[...].astype(jnp.float32),
                w_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )

        @pl.when(k == nk - 1)
        def _sign_and_pack():
            h = acc_ref[...]  # (bB, TD)
            col = j * h.shape[1] + jax.lax.broadcasted_iota(
                jnp.int32, h.shape, 1)
            # bit 1 iff H >= 0 (binarize_query: sign(0) -> +1, and
            # pack_bits packs +1 as 1); padded D columns pack as 0.
            bits = (h >= 0) & (col < n_valid_dims)
            o_ref[...] = pack_lanes(bits.astype(jnp.float32))

    return kernel


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def encode_pack(feats: Array, projection: Array, *,
                block_b: int = DEFAULT_BLOCK_B,
                interpret: bool | None = None) -> Array:
    """Fused encode + sign + bitpack: (B, f) features -> (B, Dp) uint8.

    Args:
      feats: (B, f) float features; bfloat16 rows are contracted as
        bfloat16 (the projection cast to it, exactly), others as float32.
      projection: (f, D) bipolar projection matrix M.
      block_b: batch tile height.
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (B, ceil(D/8)) uint8 packed queries, LSB-first along D with tail
      bits 0 — bit-identical to
      ``pack_rows(binarize_query(feats @ projection))``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, f = feats.shape
    f2, d = projection.shape
    assert f == f2, (feats.shape, projection.shape)

    bf16 = feats.dtype == jnp.bfloat16
    dt = jnp.bfloat16 if bf16 else jnp.float32
    bb = min(block_b, max(b, 1))
    td = min(round_up(d, TILE), MAX_TD)
    xp = pad_tiles(feats.astype(dt), bb, TILE)
    wp = pad_tiles(projection.astype(dt), TILE, td)
    gb, gf, gd = (xp.shape[0] // bb, xp.shape[1] // TILE,
                  wp.shape[1] // td)

    out = pl.pallas_call(
        _make_kernel(d, bf16),
        grid=(gb, gd, gf),
        in_specs=[
            pl.BlockSpec((bb, TILE), lambda i, j, k: (i, k)),
            pl.BlockSpec((TILE, td), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bb, td // 8), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1] // 8),
                                       jnp.uint8),
        scratch_shapes=[pltpu.VMEM((bb, td), jnp.float32)],
        name="encode_pack",
        interpret=interpret,
    )(xp, wp)
    return out[:b, : -(-d // 8)]


@functools.partial(jax.jit, static_argnames=(
    "mode", "block_b", "interpret"))
def search_from_features(feats: Array, projection: Array,
                         am_packed_t: Array, *, mode: str = "popcount",
                         block_b: int = DEFAULT_BLOCK_B,
                         interpret: bool | None = None,
                         ) -> tuple[Array, Array]:
    """Single-dispatch feature->search chain: encode_pack |> am_search_packed.

    Both Pallas kernels run inside one jit; the only intermediate is the
    (B, Dp) packed query matrix — the float H never exists.

    Args:
      feats: (B, f) float features.
      projection: (f, D) bipolar projection matrix.
      am_packed_t: (Dp, C) uint8 packed transposed AM (``pack_am``).
      mode: packed-search compute mode ("popcount" | "unpack").

    Returns:
      (best_idx, best_sim) as ``am_search_packed`` — bit-exact with the
      staged encode_query -> pack_rows -> am_search_packed chain.
    """
    n_dims = projection.shape[1]
    qp = encode_pack(feats, projection, block_b=block_b,
                     interpret=interpret)
    return am_search_packed(qp, am_packed_t, n_dims=n_dims, mode=mode,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "mode", "block_b", "interpret"))
def predict_from_features(feats: Array, projection: Array,
                          am_packed_t: Array, centroid_class: Array, *,
                          mode: str = "popcount",
                          block_b: int = DEFAULT_BLOCK_B,
                          interpret: bool | None = None) -> Array:
    """Single-dispatch feature->class pipeline (§III-D end to end).

    encode_pack |> am_search_packed |> ownership gather, one jit.
    Returns (B,) int32 predicted classes.
    """
    idx, _ = search_from_features(feats, projection, am_packed_t,
                                  mode=mode, block_b=block_b,
                                  interpret=interpret)
    return centroid_class[idx]


def imc_cycles_for(feats_shape: tuple, projection_shape: tuple) -> int:
    """128x128-array passes of the f x D projection — identical to
    ``binary_mvm``'s, so the fused encoder keeps the encoder-mapping
    cycle count of ``repro.core.imc.map_basic(f, D)`` (the pack epilogue
    rides the last accumulation step for free). A function of shapes:
    the Pallas grid takes up to 8 arrays along D per step."""
    f, d = projection_shape
    return (-(-f // TILE)) * (-(-d // TILE))
