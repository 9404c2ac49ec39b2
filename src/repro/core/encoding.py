"""Hypervector encoding modules (EM).

Two encoders, matching the paper's §II-B:

* ``projection`` — H = M^T F with a binary (bipolar +-1) random projection
  matrix M of shape (f, D). This is the encoder MEMHD itself uses because
  it is a plain MVM and therefore maps directly onto IMC arrays (and, here,
  onto 128x128 MXU tiles — see kernels/binary_mvm.py).
* ``id_level`` — H = sum_i ID_i * L_{x_i} with random bipolar ID vectors
  and thermometer-correlated Level vectors; used by the SearcHD / QuantHD /
  LeHDC baselines (Table I).

All functions are pure and jittable. Encoders are parameterised by
explicit parameter pytrees created with ``init_*`` functions.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.types import EncoderConfig

Array = jax.Array
EncoderParams = Dict[str, Array]


# ---------------------------------------------------------------------------
# Projection encoding
# ---------------------------------------------------------------------------

def init_projection(key: Array, cfg: EncoderConfig) -> EncoderParams:
    """Binary (bipolar) random projection matrix M: (f, D) in {-1, +1}."""
    m = jax.random.rademacher(key, (cfg.features, cfg.dim), dtype=jnp.float32)
    return {"projection": m}


def encode_projection(params: EncoderParams, feats: Array) -> Array:
    """H = M^T F, batched: (..., f) -> (..., D). Float accumulation at
    float32 precision: the features are not bf16-exact, and the TPU's
    default would round them (and so flip query bits that the fused
    kernel and the CPU keep)."""
    m = params["projection"]
    return jnp.einsum("...f,fd->...d", feats.astype(jnp.float32), m,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# ID-Level encoding
# ---------------------------------------------------------------------------

def _level_vectors(key: Array, levels: int, dim: int) -> Array:
    """Thermometer-correlated level hypervectors.

    L_0 is random bipolar; L_{i+1} flips a fresh disjoint slice of
    ~dim/(2(levels-1)) positions of L_i, so that L_0 and L_{levels-1} are
    ~orthogonal and intermediate levels interpolate — the standard
    construction used by the ID-Level baselines.
    """
    k0, k1 = jax.random.split(key)
    base = jax.random.rademacher(k0, (dim,), dtype=jnp.float32)
    # Random permutation determines the flip order; level i flips the
    # first floor(i * dim/2 / (levels-1)) permuted positions.
    perm = jax.random.permutation(k1, dim)
    idx = jnp.arange(dim)
    # flips_at[j] = rank of position j in the flip order
    rank = jnp.zeros((dim,), jnp.int32).at[perm].set(idx.astype(jnp.int32))
    n_flips = (jnp.arange(levels) * (dim // 2)) // max(levels - 1, 1)
    # (levels, dim): sign flip where rank < n_flips[level]
    flip = rank[None, :] < n_flips[:, None]
    return jnp.where(flip, -base[None, :], base[None, :])


def init_id_level(key: Array, cfg: EncoderConfig) -> EncoderParams:
    k_id, k_lv = jax.random.split(key)
    ids = jax.random.rademacher(
        k_id, (cfg.features, cfg.dim), dtype=jnp.float32)
    lvls = _level_vectors(k_lv, cfg.levels, cfg.dim)
    return {"ids": ids, "levels": lvls}


def quantize_features(feats: Array, levels: int) -> Array:
    """Map features (assumed in [0, 1]) to integer level indices."""
    q = jnp.clip(feats, 0.0, 1.0) * (levels - 1)
    return jnp.round(q).astype(jnp.int32)


def encode_id_level(params: EncoderParams, feats: Array,
                    *, chunk: int = 128) -> Array:
    """H = sum_i ID_i * L_{x_i}: (..., f) -> (..., D).

    Feature-chunked scan keeps the (batch, chunk, D) gather buffer small
    for large D (the 10240-D baselines).
    """
    ids, lvls = params["ids"], params["levels"]
    f, d = ids.shape
    levels = lvls.shape[0]
    x = quantize_features(feats, levels)

    batch_shape = x.shape[:-1]
    x2 = x.reshape((-1, f))
    n_chunks = -(-f // chunk)
    pad = n_chunks * chunk - f
    x_pad = jnp.pad(x2, ((0, 0), (0, pad)))
    ids_pad = jnp.pad(ids, ((0, pad), (0, 0)))
    x_c = x_pad.reshape(x2.shape[0], n_chunks, chunk)
    ids_c = ids_pad.reshape(n_chunks, chunk, d)
    # Padded feature columns gather lvls[0]; mask the gather itself to a
    # neutral (zero) level so their contribution is zero by construction
    # rather than via the zero-padded ID rows — H is invariant to the
    # chunk size for any f (asserted in tests/test_kernel_parity.py).
    valid_c = (jnp.arange(n_chunks * chunk) < f).reshape(n_chunks, chunk)

    def body(acc, args):
        xc, idc, vc = args  # (B, chunk), (chunk, D), (chunk,)
        lv = jnp.where(vc[None, :, None], lvls[xc], 0.0)  # (B, chunk, D)
        return acc + jnp.einsum("bcd,cd->bd", lv, idc), None

    acc0 = jnp.zeros((x2.shape[0], d), jnp.float32)
    acc, _ = jax.lax.scan(
        body, acc0, (jnp.swapaxes(x_c, 0, 1), ids_c, valid_c))
    return acc.reshape(*batch_shape, d)


# ---------------------------------------------------------------------------
# Unified interface
# ---------------------------------------------------------------------------

def init_encoder(key: Array, cfg: EncoderConfig) -> EncoderParams:
    if cfg.kind == "projection":
        return init_projection(key, cfg)
    return init_id_level(key, cfg)


def encode(params: EncoderParams, cfg: EncoderConfig, feats: Array) -> Array:
    """Encode features into (float) hypervectors H."""
    if cfg.kind == "projection":
        return encode_projection(params, feats)
    return encode_id_level(params, feats)


def binarize_query(h: Array) -> Array:
    """Bipolar binarization of the query hypervector: sign(H) in {-1,+1}.

    sign(0) is mapped to +1 so the output is strictly bipolar.
    """
    return jnp.where(h >= 0, 1.0, -1.0).astype(h.dtype)


def encode_query(params: EncoderParams, cfg: EncoderConfig,
                 feats: Array) -> Array:
    """Encode + (optionally) binarize — the inference-path encoder."""
    h = encode(params, cfg, feats)
    return binarize_query(h) if cfg.binarize_query else h
