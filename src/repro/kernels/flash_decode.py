"""Pallas flash-decode kernel: one-token GQA attention over a KV cache.

The serving hot loop of every attention arch's decode cell: a single
query position attends over a (possibly 32k–500k entry) cache. On TPU
the cache streams HBM→VMEM in (BLOCK, head_dim) tiles while (m, l, acc)
online-softmax state lives in VMEM scratch — the cache is read exactly
once and no (S,) score vector ever materializes in HBM.

    grid = (B, H, S/BLOCK)     # S innermost: streaming reduction
    scratch: m (1,), l (1,), acc (1, Dh)

Head-repeat for GQA (q heads / kv heads) happens through the kv
BlockSpec index_map (query head h reads kv head h // groups) — zero-copy
sharing of kv tiles across the q heads of a group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

BLOCK = 128


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    sblk = pl.program_id(2)
    nblk = pl.num_programs(2)

    @pl.when(sblk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bb = pl.program_id(0)
    qv = q_ref[0, 0, :].astype(jnp.float32)      # (Dh,)
    k = k_ref[0, 0].astype(jnp.float32)          # (BLOCK, Dh)
    v = v_ref[0, 0].astype(jnp.float32)          # (BLOCK, Dh)
    dh = qv.shape[-1]
    scale = 1.0 / (dh ** 0.5)
    s = jnp.dot(k, qv, preferred_element_type=jnp.float32) * scale

    pos = sblk * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK,), 0)
    valid = pos < len_ref[bb]
    s = jnp.where(valid, s, -jnp.inf)

    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)  # (BLOCK,)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_ref[0] * corr + jnp.sum(p)
    acc_new = acc_ref[...] * corr + jnp.dot(
        p[None, :], v, preferred_element_type=jnp.float32)  # (1, Dh)
    m_ref[0] = m_new
    l_ref[0] = l_new
    acc_ref[...] = acc_new

    @pl.when(sblk == nblk - 1)
    def _emit():
        o_ref[0, 0, :] = (acc_ref[0]
                          / jnp.maximum(l_ref[0], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode(q: Array, k_cache: Array, v_cache: Array,
                 cache_len: Array, *,
                 interpret: bool | None = None) -> Array:
    """One-token attention over the cache.

    Args:
      q: (B, H, Dh) query for the current position.
      k_cache/v_cache: (B, S, KV, Dh); S is padded to a BLOCK multiple by
        this wrapper. H % KV == 0 (GQA groups).
      cache_len: (B,) valid entries per row (keys at index >= len are
        masked).

    Returns: (B, H, Dh) attention output.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, dh = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    groups = h // kv
    pad = -s % BLOCK
    kp = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = (s + pad) // BLOCK
    # (B, S, KV, Dh) -> (B, KV, S, Dh): the streaming dim is block-major.
    kp = jnp.swapaxes(kp, 1, 2)
    vp = jnp.swapaxes(vp, 1, 2)

    return pl.pallas_call(
        _kernel,
        grid=(b, h, nblk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # cache_len (B,)
            pl.BlockSpec((1, 1, dh), lambda bb, hh, ss: (bb, hh, 0)),
            pl.BlockSpec((1, 1, BLOCK, dh),
                         lambda bb, hh, ss: (bb, hh // groups, ss, 0)),
            pl.BlockSpec((1, 1, BLOCK, dh),
                         lambda bb, hh, ss: (bb, hh // groups, ss, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dh), lambda bb, hh, ss: (bb, hh, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
        name="flash_decode",
        interpret=interpret,
    )(cache_len, q, kp, vp)
