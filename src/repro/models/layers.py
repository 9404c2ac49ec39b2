"""Transformer / SSM layer implementations (pure functions over pytrees).

Every ``init_*`` returns ``(params, axes)`` where ``axes`` mirrors the
param tree with per-dimension *logical* axis names — the sharding layer
(models/sharding.py) resolves those to mesh PartitionSpecs. Every forward
helper is shape-polymorphic over batch and works in any dtype.

Attention comes in three executions:
  * ``attention_full``    — chunked online-softmax (flash-style) causal
                            attention; O(S * chunk) live memory.
  * ``attention_local``   — sliding-window attention computed per query
                            block against a static KV neighbourhood;
                            O(S * window) FLOPs, the 5:1 gemma3 pattern's
                            cheap path.
  * ``attention_decode``  — one-token query against a KV cache.

MoE uses sort-based dropping dispatch (argsort by expert, capacity clamp,
batched expert einsum, scatter-add combine) — the standard TPU-friendly
formulation that shards experts over the "model" axis (EP).

Mamba-2 is the chunked SSD algorithm (arXiv:2405.21060) with a
constant-memory decode step.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import AttnSpec, FfnSpec, SsmSpec
from repro.models.sharding import shard_act

Array = jax.Array
Params = Dict[str, Array]
Axes = Dict[str, tuple]

# ---------------------------------------------------------------------------
# Abstract-init mode: the dry-run needs parameter *shapes* for 340B/671B
# models without allocating a byte. Inside ``abstract_init()`` every
# parameter constructor returns a ShapeDtypeStruct instead of an array;
# the logical-axes trees (static strings) are built identically.
# ---------------------------------------------------------------------------

import contextlib as _contextlib
import threading as _threading

_abstract_state = _threading.local()


@_contextlib.contextmanager
def abstract_init():
    prev = getattr(_abstract_state, "on", False)
    _abstract_state.on = True
    try:
        yield
    finally:
        _abstract_state.on = prev


def is_abstract() -> bool:
    return getattr(_abstract_state, "on", False)


def _maybe_sds(make, shape, dtype):
    if is_abstract():
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
    return make()


def _zeros(shape, dtype) -> Array:
    return _maybe_sds(lambda: jnp.zeros(shape, dtype), shape, dtype)


# ---------------------------------------------------------------------------
# Basics
# ---------------------------------------------------------------------------


def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(
        jnp.float32))).astype(dt)


def init_rms_norm(d: int, dtype) -> Tuple[Array, tuple]:
    return _zeros((d,), dtype), ("embed",)


def _dense_init(key: Array, shape, dtype, in_axis: int = 0) -> Array:
    def make():
        fan_in = shape[in_axis]
        std = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape) * std).astype(dtype)

    return _maybe_sds(make, shape, dtype)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x: (B, S, H, Dh); positions: (B, S) or (S,)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(scores: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_gqa(key: Array, d_model: int, spec: AttnSpec, dtype,
             ) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 4)
    h, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    p: Params = {
        "wq": _dense_init(ks[0], (d_model, h, dh), dtype),
        "wk": _dense_init(ks[1], (d_model, kv, dh), dtype),
        "wv": _dense_init(ks[2], (d_model, kv, dh), dtype),
        "wo": _dense_init(ks[3], (h, dh, d_model), dtype, in_axis=0),
    }
    a: Axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if spec.qkv_bias:
        p["bq"] = _zeros((h, dh), dtype)
        p["bk"] = _zeros((kv, dh), dtype)
        p["bv"] = _zeros((kv, dh), dtype)
        a["bq"] = ("heads", "head_dim")
        a["bk"] = ("kv_heads", "head_dim")
        a["bv"] = ("kv_heads", "head_dim")
    return p, a


def _qkv(p: Params, spec: AttnSpec, x: Array, positions: Array,
         ) -> Tuple[Array, Array, Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if spec.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = rope(q, positions, spec.rope_theta)
    k = rope(k, positions, spec.rope_theta)
    return q, k, v


def _repeat_kv(k: Array, groups: int) -> Array:
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def attention_full(q: Array, k: Array, v: Array, *, q_offset: int = 0,
                   softcap: Optional[float] = None,
                   chunk: int = 1024) -> Array:
    """Chunked causal attention with online softmax.

    q: (B, Sq, H, Dh); k, v: (B, Skv, H, Dh) (kv already head-repeated).
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill
    continuation); causal mask is (q_offset + i) >= j.
    """
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]  # may differ from dh (MLA)
    scale = 1.0 / math.sqrt(dh)
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = kp.reshape(b, n_chunks, chunk, h, dh)
    vc = vp.reshape(b, n_chunks, chunk, h, dv)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, cidx = inputs
        k_pos = cidx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb) * scale
        s = _softcap(s, softcap)
        mask = (q_pos[:, None] >= k_pos[None, :]) & (
            k_pos[None, :] < skv)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Guard fully-masked rows (exp(-inf - -inf)).
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), corr, 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)  # f32 accumulator
    # Remat the chunk body: the backward pass recomputes each chunk's
    # (Sq, chunk) score/prob block instead of keeping all of them live —
    # the flash-attention memory contract, expressed at the JAX level.
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (jnp.swapaxes(kc, 0, 1), jnp.swapaxes(vc, 0, 1),
         jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def attention_local(q: Array, k: Array, v: Array, window: int,
                    *, softcap: Optional[float] = None,
                    block: int = 512) -> Array:
    """Sliding-window causal attention (training/prefill path).

    Query block i attends keys [i*block - window, i*block + block): a
    static-size neighbourhood, so total FLOPs are O(S * (window + block))
    rather than O(S^2).
    """
    b, s, h, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    block = min(block, s)
    n_blocks = -(-s // block)
    pad_q = n_blocks * block - s
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    # KV padded on the left by `window` so every block's neighbourhood is
    # in-range, and on the right to the padded q length.
    kp = jnp.pad(k, ((0, 0), (window, pad_q), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, pad_q), (0, 0), (0, 0)))
    span = window + block

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * block, block, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(kp, i * block, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, i * block, span, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) * scale
        sc = _softcap(sc, softcap)
        q_pos = i * block + jnp.arange(block)          # absolute
        k_pos = i * block - window + jnp.arange(span)  # absolute
        # Window semantics: attend to the last `window` keys *including*
        # self (diff in [0, window)) — matches the decode ring buffer.
        mask = ((q_pos[:, None] >= k_pos[None, :])
                & (q_pos[:, None] - k_pos[None, :] < window)
                & (k_pos[None, :] >= 0) & (q_pos[:, None] < s)
                & (k_pos[None, :] < s))
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        m = sc.max(axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(sc - m)
        p = jnp.where(jnp.isfinite(sc), p, 0.0)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb)
        denom = p.sum(axis=-1).transpose(0, 2, 1)[..., None]
        return o / jnp.maximum(denom, 1e-20).astype(o.dtype)

    # Remat per block: backward recomputes each block's score window.
    outs = jax.lax.map(jax.checkpoint(one_block),
                       jnp.arange(n_blocks))  # (nb, B, block, H, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, n_blocks * block, h, dh)
    return out[:, :s]


def attention_decode(q: Array, k_cache: Array, v_cache: Array,
                     cache_len: Array, *,
                     softcap: Optional[float] = None) -> Array:
    """Single-position decode: q (B, 1, H, Dh) vs cache (B, S, H, Dh).

    ``cache_len``: (B,) or scalar count of valid cache entries (the new
    token's k/v must already be written at cache_len - 1).
    """
    dh = q.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache) * scale
    s = _softcap(s, softcap)
    k_pos = jnp.arange(k_cache.shape[1])
    valid = k_pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v_cache)
    return out


def gqa_forward(p: Params, spec: AttnSpec, x: Array, positions: Array,
                ) -> Array:
    """Training/prefill GQA attention over hidden states x: (B, S, D)."""
    q, k, v = _qkv(p, spec, x, positions)
    q = shard_act(q, ("batch", "seq", "act_heads", None))
    groups = spec.n_heads // spec.n_kv_heads
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    if spec.window is not None and x.shape[1] > spec.window:
        out = attention_local(q, k, v, spec.window,
                              softcap=spec.logit_softcap)
    else:
        out = attention_full(q, k, v, softcap=spec.logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def attention_decode_seqpar(q: Array, k_cache: Array, v_cache: Array,
                            k_new: Array, v_new: Array, slot: Array,
                            cache_len: Array, rules, *,
                            softcap: Optional[float] = None,
                            ) -> Tuple[Array, Array, Array]:
    """Sequence-parallel flash decode over a seq-sharded KV cache.

    The caches are sharded on their seq dim over "model". Instead of
    letting GSPMD all-gather the (possibly 500k-token) cache to every
    chip, each shard computes online-softmax partials (m, l, acc) over
    its local slice and the merge is three tiny psums — the flash-decode
    pattern. The new token's (k, v) is scattered into whichever shard
    owns ``slot``.

    Args:
      q: (B, 1, H, Dh) replicated query (kv already head-repeated
        upstream is NOT required — pass kv-head tensors and repeat
        inside to keep wire small).
      k_cache/v_cache: (B, S, KV, Dh), S sharded over "model".
      k_new/v_new: (B, KV, Dh) this step's entries.
      slot: (B,) global cache slot to write.
      cache_len: (B,) valid entries after the write.

    Returns:
      (out, new_k_cache, new_v_cache): out (B, 1, H, Dh).
    """
    mesh = rules.mesh
    b, _, h, dh = q.shape
    s_global = k_cache.shape[1]
    kv = k_cache.shape[2]
    groups = h // kv
    m_size = mesh.shape["model"]
    s_local = s_global // m_size
    ba = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    from jax.sharding import PartitionSpec as P

    def bspec(*rest):
        lead = ba if b % _axes_size(mesh, ba) == 0 else None
        return P(lead, *rest)

    def local(q_l, kc, vc, kn, vn, slot_l, len_l):
        # kc/vc: (B, s_local, KV, Dh) local slice; offset from rank.
        rank = jax.lax.axis_index("model")
        offset = rank * s_local
        local_slot = slot_l - offset
        in_range = (local_slot >= 0) & (local_slot < s_local)
        li = jnp.clip(local_slot, 0, s_local - 1)
        bidx = jnp.arange(kc.shape[0])
        kc = kc.at[bidx, li].set(
            jnp.where(in_range[:, None, None], kn, kc[bidx, li]))
        vc = vc.at[bidx, li].set(
            jnp.where(in_range[:, None, None], vn, vc[bidx, li]))

        kk = _repeat_kv(kc, groups)
        vv = _repeat_kv(vc, groups)
        scale = 1.0 / math.sqrt(dh)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_l, kk) * scale
        s = _softcap(s, softcap)
        k_pos = offset + jnp.arange(s_local)
        valid = k_pos[None, :] < len_l[:, None]
        s = jnp.where(valid[:, None, None, :], s.astype(jnp.float32),
                      -jnp.inf)
        m_l = jnp.max(s, axis=-1)                      # (B,H,1)
        m_g = jax.lax.pmax(m_l, "model")
        m_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        l_l = p.sum(axis=-1)
        acc_l = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vv.dtype), vv
                           ).astype(jnp.float32)
        l_g = jax.lax.psum(l_l, "model")
        acc_g = jax.lax.psum(acc_l, "model")
        out = (acc_g / jnp.maximum(l_g[..., None], 1e-20)).astype(q_l.dtype)
        return jnp.einsum("bhqd->bqhd", out), kc, vc

    out, new_k, new_v = jax.shard_map(
        local, mesh=mesh,
        in_specs=(bspec(None, None, None), bspec("model", None, None),
                  bspec("model", None, None), bspec(None, None),
                  bspec(None, None), bspec(), bspec()),
        out_specs=(bspec(None, None, None), bspec("model", None, None),
                   bspec("model", None, None)),
    )(q, k_cache, v_cache, k_new, v_new, slot, cache_len)
    return out, new_k, new_v


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def gqa_decode(p: Params, spec: AttnSpec, x: Array, cache: Dict[str, Array],
               *, seq_parallel: bool = False,
               ) -> Tuple[Array, Dict[str, Array]]:
    """One-token decode. x: (B, 1, D); cache: {k, v, len}.

    cache["k"/"v"]: (B, S_cache, KV, Dh) — ring buffer when the layer is
    windowed (S_cache == window), linear otherwise. With ``seq_parallel``
    (and active sharding rules with seq-sharded caches) the attention
    runs shard-locally with psum merges (flash decode).
    """
    b = x.shape[0]
    pos = cache["len"]  # (B,) absolute position of the new token
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if spec.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, pos[:, None], spec.rope_theta)
    k = rope(k, pos[:, None], spec.rope_theta)

    s_cache = cache["k"].shape[1]
    slot = (pos % s_cache if spec.window is not None else pos)  # (B,)
    valid = jnp.minimum(pos + 1, s_cache)

    from repro.models import sharding as sh_mod
    rules = sh_mod.current_rules()
    use_seqpar = (seq_parallel and rules is not None
                  and rules.shard_seq and "model" in rules.mesh.axis_names
                  and s_cache % rules.mesh.shape["model"] == 0
                  and spec.window is None)
    if use_seqpar:
        out, k_cache, v_cache = attention_decode_seqpar(
            q, cache["k"], cache["v"], k[:, 0], v[:, 0], slot, valid,
            rules, softcap=spec.logit_softcap)
    else:
        bidx = jnp.arange(b)
        k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
        v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
        groups = spec.n_heads // spec.n_kv_heads
        kk = _repeat_kv(k_cache, groups)
        vv = _repeat_kv(v_cache, groups)
        out = attention_decode(q, kk, vv, valid,
                               softcap=spec.logit_softcap)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": k_cache, "v": v_cache, "len": pos + 1}


def init_gqa_cache(spec: AttnSpec, batch: int, max_len: int, dtype,
                   quant: bool = False) -> Dict[str, Array]:
    s = min(max_len, spec.window) if spec.window is not None else max_len
    shape = (batch, s, spec.n_kv_heads, spec.head_dim)
    if quant:
        # int8 rows + per-(batch, pos, kv-head) float16 scales: ~1.03
        # bytes/element vs 2 for bf16.
        return {
            "k_q": jnp.zeros(shape, jnp.int8),
            "v_q": jnp.zeros(shape, jnp.int8),
            "k_s": jnp.zeros(shape[:3], jnp.float16),
            "v_s": jnp.zeros(shape[:3], jnp.float16),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def _quant_rows(x: Array) -> Tuple[Array, Array]:
    """Per-(..., head) symmetric int8 quantization over head_dim."""
    scale = jnp.max(jnp.abs(x), axis=-1) / 127.0 + 1e-8  # (..., H)
    q = jnp.clip(jnp.round(x / scale[..., None]), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def gqa_decode_quant(p: Params, spec: AttnSpec, x: Array,
                     cache: Dict[str, Array],
                     ) -> Tuple[Array, Dict[str, Array]]:
    """One-token decode against an int8 KV cache.

    Exact-algebra dequant: scores = (q . k_int8) * k_scale (the per-row
    scale factors out of the head_dim dot), and the value product applies
    v_scale to the attention probabilities before the int8 PV einsum —
    no materialized dequantized cache.
    """
    b = x.shape[0]
    pos = cache["len"]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if spec.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, pos[:, None], spec.rope_theta)
    k = rope(k, pos[:, None], spec.rope_theta)

    s_cache = cache["k_q"].shape[1]
    slot = (pos % s_cache if spec.window is not None else pos)
    bidx = jnp.arange(b)
    k_new_q, k_new_s = _quant_rows(k[:, 0])
    v_new_q, v_new_s = _quant_rows(v[:, 0])
    k_q = cache["k_q"].at[bidx, slot].set(k_new_q)
    v_q = cache["v_q"].at[bidx, slot].set(v_new_q)
    k_s = cache["k_s"].at[bidx, slot].set(k_new_s)
    v_s = cache["v_s"].at[bidx, slot].set(v_new_s)

    groups = spec.n_heads // spec.n_kv_heads
    kk = _repeat_kv(k_q, groups)                      # int8 (B,S,H,D)
    kk_s = _repeat_kv(k_s[..., None], groups)[..., 0]  # (B,S,H)
    vv = _repeat_kv(v_q, groups)
    vv_s = _repeat_kv(v_s[..., None], groups)[..., 0]

    scale = 1.0 / math.sqrt(spec.head_dim)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32))
    s = s * jnp.moveaxis(kk_s.astype(jnp.float32), -1, 1)[:, :, None, :]
    s = _softcap(s * scale, spec.logit_softcap)
    k_pos = jnp.arange(s_cache)
    valid = jnp.minimum(pos + 1, s_cache)
    mask = k_pos[None, :] < valid[:, None]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    pw = jax.nn.softmax(s, axis=-1)
    pw = pw * jnp.moveaxis(vv_s.astype(jnp.float32), -1, 1)[:, :, None, :]
    out = jnp.einsum("bhqk,bkhd->bqhd", pw, vv.astype(jnp.float32))
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return y, {"k_q": k_q, "v_q": v_q, "k_s": k_s, "v_s": v_s,
               "len": pos + 1}


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek V2/V3)
# ---------------------------------------------------------------------------

def init_mla(key: Array, d_model: int, spec: AttnSpec, dtype,
             ) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 8)
    h = spec.n_heads
    qk = spec.qk_nope_dim + spec.qk_rope_dim
    p: Params = {}
    a: Axes = {}
    if spec.q_lora_rank:
        p["wq_a"] = _dense_init(ks[0], (d_model, spec.q_lora_rank), dtype)
        p["q_norm"] = _zeros((spec.q_lora_rank,), dtype)
        p["wq_b"] = _dense_init(ks[1], (spec.q_lora_rank, h, qk), dtype)
        a["wq_a"] = ("embed", "lora")
        a["q_norm"] = ("lora",)
        a["wq_b"] = ("lora", "heads", "head_dim")
    else:
        p["wq"] = _dense_init(ks[0], (d_model, h, qk), dtype)
        a["wq"] = ("embed", "heads", "head_dim")
    # Joint compressed KV + decoupled rope key.
    p["wkv_a"] = _dense_init(
        ks[2], (d_model, spec.kv_lora_rank + spec.qk_rope_dim), dtype)
    p["kv_norm"] = _zeros((spec.kv_lora_rank,), dtype)
    p["wk_b"] = _dense_init(
        ks[3], (spec.kv_lora_rank, h, spec.qk_nope_dim), dtype)
    p["wv_b"] = _dense_init(
        ks[4], (spec.kv_lora_rank, h, spec.v_head_dim), dtype)
    p["wo"] = _dense_init(ks[5], (h, spec.v_head_dim, d_model), dtype)
    a.update({
        "wkv_a": ("embed", "lora"),
        "kv_norm": ("lora",),
        "wk_b": ("lora", "heads", "head_dim"),
        "wv_b": ("lora", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    })
    return p, a


def _mla_q(p: Params, spec: AttnSpec, x: Array, positions: Array,
           eps: float) -> Tuple[Array, Array]:
    """Returns (q_nope, q_rope): (B,S,H,nope), (B,S,H,rope)."""
    if spec.q_lora_rank:
        ql = x @ p["wq_a"]
        ql = rms_norm(ql, p["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bshk", ql, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., : spec.qk_nope_dim]
    q_rope = rope(q[..., spec.qk_nope_dim:], positions, spec.rope_theta)
    return q_nope, q_rope


def mla_forward(p: Params, spec: AttnSpec, x: Array, positions: Array,
                eps: float = 1e-5) -> Array:
    """Prefill/training MLA: materialize per-head K/V from the latent."""
    q_nope, q_rope = _mla_q(p, spec, x, positions, eps)
    kv = x @ p["wkv_a"]  # (B, S, lora + rope)
    c_kv = rms_norm(kv[..., : spec.kv_lora_rank], p["kv_norm"], eps)
    k_rope = rope(kv[..., spec.kv_lora_rank:][:, :, None, :], positions,
                  spec.rope_theta)  # (B, S, 1, rope)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    h = spec.n_heads
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1]
                                  + (spec.qk_rope_dim,))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attention_full(q, k, v)  # v head dim differs from qk dim — ok
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_decode(p: Params, spec: AttnSpec, x: Array, cache: Dict[str, Array],
               eps: float = 1e-5) -> Tuple[Array, Dict[str, Array]]:
    """Absorbed-form MLA decode against the compressed latent cache.

    cache["ckv"]: (B, S, kv_lora); cache["krope"]: (B, S, rope).
    Scores = q_nope @ W_UK^T @ c_kv + q_rope @ k_rope  (W_UK absorbed into
    the query), so per-token cache is kv_lora + rope floats — the whole
    point of MLA.
    """
    b = x.shape[0]
    pos = cache["len"]
    q_nope, q_rope = _mla_q(p, spec, x, pos[:, None], eps)
    kv = x @ p["wkv_a"]
    c_new = rms_norm(kv[..., : spec.kv_lora_rank], p["kv_norm"], eps)
    kr_new = rope(kv[..., spec.kv_lora_rank:][:, :, None, :], pos[:, None],
                  spec.rope_theta)[:, :, 0, :]

    bidx = jnp.arange(b)
    ckv = cache["ckv"].at[bidx, pos].set(c_new[:, 0])
    krope = cache["krope"].at[bidx, pos].set(kr_new[:, 0])

    # Absorb W_UK into q: (B,1,H,nope) x (lora,H,nope) -> (B,1,H,lora)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
    scale = 1.0 / math.sqrt(spec.qk_nope_dim + spec.qk_rope_dim)
    s = (jnp.einsum("bshr,btr->bhst", q_abs, ckv)
         + jnp.einsum("bshk,btk->bhst", q_rope, krope)) * scale
    k_pos = jnp.arange(ckv.shape[1])
    valid = k_pos[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    pw = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", pw, ckv)  # (B,1,H,lora)
    out = jnp.einsum("bshr,rhk->bshk", ctx, p["wv_b"])  # absorb W_UV
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"ckv": ckv, "krope": krope, "len": pos + 1}


def init_mla_cache(spec: AttnSpec, batch: int, max_len: int, dtype,
                   ) -> Dict[str, Array]:
    return {
        "ckv": jnp.zeros((batch, max_len, spec.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, spec.qk_rope_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Cross attention (musicgen conditioning)
# ---------------------------------------------------------------------------

def init_cross_attn(key: Array, d_model: int, spec: AttnSpec, dtype,
                    ) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 4)
    h, dh = spec.n_heads, spec.head_dim
    p = {
        "wq": _dense_init(ks[0], (d_model, h, dh), dtype),
        "wk": _dense_init(ks[1], (d_model, h, dh), dtype),
        "wv": _dense_init(ks[2], (d_model, h, dh), dtype),
        "wo": _dense_init(ks[3], (h, dh, d_model), dtype),
    }
    a = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "heads", "head_dim"),
        "wv": ("embed", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return p, a


def cross_attn_forward(p: Params, spec: AttnSpec, x: Array, cond: Array,
                       ) -> Array:
    """x: (B, S, D) attends over cond: (B, T, D) (no mask, no rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", cond, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", cond, p["wv"])
    s = jnp.einsum("bshk,bthk->bhst", q, k) / math.sqrt(spec.head_dim)
    pw = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthk->bshk", pw, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# FFN: dense + MoE
# ---------------------------------------------------------------------------

def _act(name: str, gate: Array, up: Optional[Array]) -> Array:
    if name == "silu_glu":
        return jax.nn.silu(gate) * up
    if name == "gelu_glu":
        return jax.nn.gelu(gate) * up
    if name == "gelu":
        return jax.nn.gelu(gate)
    if name == "squared_relu":
        r = jax.nn.relu(gate)
        return r * r
    raise ValueError(name)


def init_dense_ffn(key: Array, d_model: int, spec: FfnSpec, dtype,
                   ) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 3)
    glu = spec.activation.endswith("_glu")
    p: Params = {"w_in": _dense_init(ks[0], (d_model, spec.d_ff), dtype),
                 "w_out": _dense_init(ks[1], (spec.d_ff, d_model), dtype)}
    a: Axes = {"w_in": ("embed", "mlp"), "w_out": ("mlp", "embed")}
    if glu:
        p["w_up"] = _dense_init(ks[2], (d_model, spec.d_ff), dtype)
        a["w_up"] = ("embed", "mlp")
    return p, a


def dense_ffn(p: Params, spec: FfnSpec, x: Array) -> Array:
    gate = x @ p["w_in"]
    gate = shard_act(gate, ("batch", "seq", "act_mlp"))
    up = x @ p["w_up"] if "w_up" in p else None
    h = _act(spec.activation, gate, up)
    return h @ p["w_out"]


def init_moe_ffn(key: Array, d_model: int, spec: FfnSpec, dtype,
                 ) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 7)
    e, f = spec.n_experts, spec.d_ff_expert
    p: Params = {
        "router": _dense_init(ks[0], (d_model, e), jnp.float32),
        "w_gate": _dense_init(ks[1], (e, d_model, f), dtype),
        "w_up": _dense_init(ks[2], (e, d_model, f), dtype),
        "w_down": _dense_init(ks[3], (e, f, d_model), dtype),
    }
    a: Axes = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_mlp"),
        "w_up": ("experts", "embed", "expert_mlp"),
        "w_down": ("experts", "expert_mlp", "embed"),
    }
    if spec.router == "sigmoid":
        p["router_bias"] = _zeros((e,), jnp.float32)
        a["router_bias"] = (None,)
    if spec.n_shared:
        fs = spec.n_shared * f
        p["ws_gate"] = _dense_init(ks[4], (d_model, fs), dtype)
        p["ws_up"] = _dense_init(ks[5], (d_model, fs), dtype)
        p["ws_down"] = _dense_init(ks[6], (fs, d_model), dtype)
        a["ws_gate"] = ("embed", "mlp")
        a["ws_up"] = ("embed", "mlp")
        a["ws_down"] = ("mlp", "embed")
    return p, a


def moe_ffn(p: Params, spec: FfnSpec, x: Array,
            ) -> Tuple[Array, Dict[str, Array]]:
    """Top-k MoE dispatcher. x: (B, S, D) -> (y, aux).

    Two executions:
      * sharded (production): when sharding rules with a "model" axis are
        active, dispatch runs under shard_map with an explicit
        all-to-all over the expert axis — the only formulation GSPMD
        maps efficiently at E=256 (the pure-scatter version degenerates
        into full-buffer all-reduces; see EXPERIMENTS.md §Perf).
      * local: single-device sort-based dispatch (tests, smoke configs).

    aux carries the load-balance loss (softmax router) or the per-expert
    token counts (sigmoid router — the train loop applies DeepSeek-V3's
    aux-free bias update with them).
    """
    from repro.models import sharding as sh_mod
    rules = sh_mod.current_rules()
    if rules is not None and "model" in rules.mesh.axis_names:
        return _moe_ffn_sharded(p, spec, x, rules)
    return _moe_ffn_local(p, spec, x)


def _moe_ffn_local(p: Params, spec: FfnSpec, x: Array,
                   ) -> Tuple[Array, Dict[str, Array]]:
    """Single-device sort-based top-k dispatch (the reference semantics)."""
    b, s, d = x.shape
    e, k = spec.n_experts, spec.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])  # (T, E)
    if spec.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel_scores = scores + p["router_bias"]  # bias only affects choice
        _, top_i = jax.lax.top_k(sel_scores, k)
        top_w = jnp.take_along_axis(scores, top_i, axis=1)
        top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(scores, k)
        top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)

    # ---- sort-based dispatch ------------------------------------------------
    # Small token counts (decode steps, smoke tests) get worst-case
    # capacity == t: exact dropless routing for the serving path. At
    # training scale the capacity-factor formula bounds the buffer.
    if t * k <= 4096:
        cap = t
    else:
        cap = max(1, int(math.ceil(t * k / e * spec.capacity_factor)))
    flat_e = top_i.reshape(-1)                      # (T*k,)
    order = jnp.argsort(flat_e)                     # stable
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos_in_seg = jnp.arange(t * k) - seg_start[sorted_e]
    keep = pos_in_seg < cap
    dest = jnp.where(keep, sorted_e * cap + pos_in_seg, e * cap)

    tok_idx = order // k                            # source token per slot
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xt[tok_idx])
    buf = shard_act(buf[: e * cap].reshape(e, cap, d),
                    ("act_experts", None, None))

    # ---- expert computation (batched einsum; experts shard over model) -----
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(gate) * up
    y_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    # ---- combine -----------------------------------------------------------------
    y_flat = y_e.reshape(e * cap, d)
    y_slots = jnp.where(keep[:, None],
                        y_flat[jnp.minimum(dest, e * cap - 1)], 0.0)
    w_slots = top_w.reshape(-1)[order][:, None].astype(y_slots.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok_idx].add(y_slots * w_slots)

    # ---- shared experts ---------------------------------------------------------
    if spec.n_shared:
        sh = jax.nn.silu(xt @ p["ws_gate"]) * (xt @ p["ws_up"])
        y = y + sh @ p["ws_down"]

    # ---- aux --------------------------------------------------------------------
    counts = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0)
    if spec.router == "sigmoid":
        aux = {"expert_counts": counts}
    else:
        # Switch-style load-balance loss.
        frac_tokens = counts / (t * k)
        frac_probs = scores.mean(axis=0)
        aux = {"lb_loss": e * jnp.sum(frac_tokens * frac_probs),
               "expert_counts": counts}
    return y.reshape(b, s, d), aux


def _route(logits: Array, spec: FfnSpec, router_bias: Optional[Array],
           ) -> Tuple[Array, Array, Array]:
    """(scores, top_w, top_i) for either router flavour."""
    if spec.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + (router_bias if router_bias is not None else 0.0)
        _, top_i = jax.lax.top_k(sel, spec.top_k)
        top_w = jnp.take_along_axis(scores, top_i, axis=1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(scores, spec.top_k)
    top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)
    return scores, top_w, top_i


def _moe_ffn_sharded(p: Params, spec: FfnSpec, x: Array, rules,
                     ) -> Tuple[Array, Dict[str, Array]]:
    """Expert-parallel MoE: shard_map + all-to-all over the "model" axis.

    Tokens are flattened to (T, d) and sharded over *all* mesh axes;
    experts are sharded over "model". Each device routes its local
    tokens, packs per-(source, expert) capacity buffers, all-to-alls
    them to the expert owners along "model", runs its local experts as
    one batched einsum, and all-to-alls results back. Wire cost per
    layer is O(T_local * k * cf * d) — independent of E — instead of the
    O(E * cap * d) full-buffer reductions GSPMD generates for scattered
    dispatch.
    """
    mesh = rules.mesh
    all_axes = tuple(mesh.axis_names)
    e, k = spec.n_experts, spec.top_k
    b, s, d = x.shape
    t = b * s
    n_dev = mesh.devices.size
    # Expert-parallel axes come from the rules table ("experts" entry):
    # ("model",) by default; ("model", "data") gives full EP (one expert
    # per chip at E == n_devices) with no FSDP gathers on expert weights
    # — §Perf iteration D4.
    exp_axes = tuple(a for a in (rules.table().get("experts") or ("model",))
                     if a in mesh.axis_names)
    m_size = 1
    for a in exp_axes:
        m_size *= mesh.shape[a]
    if e % m_size:  # fall back to the largest dividing prefix
        exp_axes = ("model",)
        m_size = mesh.shape["model"]
    e_local = e // m_size
    assert e % m_size == 0, (e, m_size)
    a2a_axis = exp_axes if len(exp_axes) > 1 else exp_axes[0]

    pad_t = -t % n_dev
    xt = x.reshape(t, d)
    if pad_t:
        xt = jnp.concatenate(
            [xt, jnp.zeros((pad_t, d), x.dtype)], axis=0)
    t_pad = t + pad_t
    t_local = t_pad // n_dev
    # Per-(source-device, expert) capacity.
    cap = max(1, int(math.ceil(t_local * k / e * spec.capacity_factor)))

    router_bias = p.get("router_bias")
    from jax.sharding import PartitionSpec as P

    def local_fn(xt_l, router, bias, wg, wu, wd):
        # xt_l: (t_local, d); wg/wu/wd: (e_local, ..., ...)
        logits = xt_l.astype(jnp.float32) @ router
        scores, top_w, top_i = _route(
            logits, spec, bias[0] if bias is not None else None)

        flat_e = top_i.reshape(-1)                      # (t_local*k,)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
        pos = jnp.arange(t_local * k) - seg_start[sorted_e]
        keep = pos < cap
        dest = jnp.where(keep, sorted_e * cap + pos, e * cap)
        tok = order // k

        buf = jnp.zeros((e * cap + 1, d), xt_l.dtype
                        ).at[dest].set(xt_l[tok])[:-1]
        # (e, cap, d) -> regroup by destination model-rank and exchange.
        buf = buf.reshape(m_size, e_local * cap, d)
        recv = jax.lax.all_to_all(buf, a2a_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv: (m_size * e_local * cap, d) grouped as (src, e_local, cap).
        hbuf = recv.reshape(m_size, e_local, cap, d)
        hbuf = jnp.moveaxis(hbuf, 1, 0).reshape(e_local, m_size * cap, d)

        gate = jnp.einsum("ecd,edf->ecf", hbuf, wg)
        up = jnp.einsum("ecd,edf->ecf", hbuf, wu)
        h = jax.nn.silu(gate) * up
        y_e = jnp.einsum("ecf,efd->ecd", h, wd)

        # Route results back to their source devices.
        y_e = y_e.reshape(e_local, m_size, cap, d)
        y_e = jnp.moveaxis(y_e, 1, 0).reshape(m_size, e_local * cap, d)
        back = jax.lax.all_to_all(y_e, a2a_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        y_buf = back.reshape(e * cap, d)
        y_slots = jnp.where(keep[:, None],
                            y_buf[jnp.minimum(dest, e * cap - 1)], 0.0)
        w_slots = top_w.reshape(-1)[order][:, None].astype(y_slots.dtype)
        y_l = jnp.zeros((t_local, d), x.dtype).at[tok].add(
            y_slots * w_slots)

        counts_l = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0)
        counts = jax.lax.psum(counts_l, all_axes)
        probs_mean = jax.lax.pmean(scores.mean(axis=0), all_axes)
        return y_l, counts, probs_mean

    bias_in = (router_bias[None] if router_bias is not None
               else jnp.zeros((1, e), jnp.float32))
    y_flat, counts, probs_mean = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(all_axes, None), P(), P(), P(exp_axes),
                  P(exp_axes), P(exp_axes)),
        out_specs=(P(all_axes, None), P(), P()),
    )(xt, p["router"], bias_in, p["w_gate"], p["w_up"], p["w_down"])

    y = y_flat[:t].reshape(b, s, d)

    if spec.n_shared:
        xt2 = x.reshape(t, d)
        sh = jax.nn.silu(xt2 @ p["ws_gate"]) * (xt2 @ p["ws_up"])
        y = y + (sh @ p["ws_down"]).reshape(b, s, d)

    if spec.router == "sigmoid":
        aux = {"expert_counts": counts}
    else:
        frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
        aux = {"lb_loss": e * jnp.sum(frac_tokens * probs_mean),
               "expert_counts": counts}
    return y, aux


# ---------------------------------------------------------------------------
# Mamba-2 (SSD)
# ---------------------------------------------------------------------------

def init_ssm(key: Array, d_model: int, spec: SsmSpec, dtype,
             ) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 5)
    d_in = spec.expand * d_model
    n_heads = d_in // spec.head_dim
    conv_dim = d_in + 2 * spec.n_groups * spec.d_state
    # in_proj emits [z (gate), x, B, C, dt].
    d_proj = 2 * d_in + 2 * spec.n_groups * spec.d_state + n_heads
    p: Params = {
        "w_in": _dense_init(ks[0], (d_model, d_proj), dtype),
        "conv_w": _dense_init(ks[1], (spec.conv_width, conv_dim), dtype),
        "conv_b": _zeros((conv_dim,), dtype),
        "a_log": _maybe_sds(
            lambda: jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(dtype),
            (n_heads,), dtype),
        "d_skip": _maybe_sds(lambda: jnp.ones((n_heads,), dtype),
                             (n_heads,), dtype),
        "dt_bias": _maybe_sds(
            lambda: jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                ks[2], (n_heads,),
                minval=math.log(spec.dt_min),
                maxval=math.log(spec.dt_max))))).astype(dtype),
            (n_heads,), dtype),
        "gate_norm": _zeros((d_in,), dtype),
        "w_out": _dense_init(ks[3], (d_in, d_model), dtype),
    }
    a: Axes = {
        "w_in": ("embed", "ssm_inner"),
        "conv_w": ("conv", "ssm_inner"),
        "conv_b": ("ssm_inner",),
        "a_log": (None,),
        "d_skip": (None,),
        "dt_bias": (None,),
        "gate_norm": ("ssm_inner",),
        "w_out": ("ssm_inner", "embed"),
    }
    return p, a


def _ssm_split(p: Params, spec: SsmSpec, d_model: int, proj: Array):
    d_in = spec.expand * d_model
    gn = spec.n_groups * spec.d_state
    n_heads = d_in // spec.head_dim
    z = proj[..., :d_in]
    xbc = proj[..., d_in: d_in + d_in + 2 * gn]
    dt = proj[..., -n_heads:]
    return z, xbc, dt


def _causal_conv(xbc: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv, width W. xbc: (B, S, C)."""
    width = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xp[:, i: i + xbc.shape[1]] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def ssd_forward(p: Params, spec: SsmSpec, d_model: int, x: Array) -> Array:
    """Chunked SSD (Mamba-2). x: (B, S, D) -> (B, S, D)."""
    b, s, _ = x.shape
    d_in = spec.expand * d_model
    n_heads = d_in // spec.head_dim
    g, n, ph = spec.n_groups, spec.d_state, spec.head_dim

    proj = x @ p["w_in"]
    z, xbc, dt = _ssm_split(p, spec, d_model, proj)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xs = xbc[..., :d_in].reshape(b, s, n_heads, ph)
    bmat = xbc[..., d_in: d_in + g * n].reshape(b, s, g, n)
    cmat = xbc[..., d_in + g * n:].reshape(b, s, g, n)
    heads_per_g = n_heads // g
    bmat = jnp.repeat(bmat, heads_per_g, axis=2)  # (B,S,H,N)
    cmat = jnp.repeat(cmat, heads_per_g, axis=2)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B,S,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))  # (H,)
    da = dt * a  # (B,S,H) log-decay per step

    q = min(spec.chunk, s)
    n_chunks = -(-s // q)
    pad = n_chunks * q - s

    def pad_t(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    xs_c = pad_t(xs).reshape(b, n_chunks, q, n_heads, ph)
    b_c = pad_t(bmat).reshape(b, n_chunks, q, n_heads, n)
    c_c = pad_t(cmat).reshape(b, n_chunks, q, n_heads, n)
    dt_c = pad_t(dt).reshape(b, n_chunks, q, n_heads)
    da_c = pad_t(da).reshape(b, n_chunks, q, n_heads)

    # ONE fused scan over chunks: intra-chunk attention, inter-chunk
    # state carry, and output — the (Q, Q) decay matrix exists for a
    # single chunk at a time (materializing it for all chunks at once is
    # O(S*Q) memory and was the dominant HBM term in the first dry-run
    # baseline; see EXPERIMENTS.md §Perf). State-path math stays float32
    # (long decay products underflow bf16). The body is remat'd so the
    # backward pass re-derives each chunk's decay instead of storing it.
    mask = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]

    def chunk_body(s_prev, inputs):
        # s_prev: (B,H,N,P) f32 state entering this chunk.
        xs_k, b_k, c_k, dt_k, da_k = inputs  # (B,Q,H,*) per-chunk slices
        cum = jnp.cumsum(da_k, axis=1)       # (B,Q,H)
        seg_total = cum[:, -1]               # (B,H)
        xdt = xs_k.astype(jnp.float32) * dt_k[..., None]
        b32 = b_k.astype(jnp.float32)
        c32 = c_k.astype(jnp.float32)

        # Intra-chunk: L[i,j] = exp(cum_i - cum_j), i >= j.
        decay = jnp.where(
            mask, jnp.exp(cum[:, :, None, :] - cum[:, None, :, :]), 0.0)
        cb = jnp.einsum("bqhn,bkhn->bqkh", c32, b32)
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", cb * decay, xdt)

        # Inter-chunk: contribution of the carried state.
        in_decay = jnp.exp(cum)  # (B,Q,H)
        y_inter = jnp.einsum("bqhn,bhnp->bqhp",
                             c32 * in_decay[..., None], s_prev)

        # Next state: S' = exp(seg_total) * S + sum_j exp(total-cum_j) B_j xdt_j^T
        state_decay = jnp.exp(seg_total[:, None, :] - cum)  # (B,Q,H)
        bx = jnp.einsum("bqhn,bqhp->bhnp",
                        b32 * state_decay[..., None], xdt)
        s_new = s_prev * jnp.exp(seg_total)[..., None, None] + bx
        return s_new, (y_intra + y_inter).astype(xs.dtype)

    def to_scan(t):  # (B,Cn,Q,...) -> (Cn,B,Q,...)
        return jnp.moveaxis(t, 1, 0)

    s0 = jnp.zeros((b, n_heads, n, ph), jnp.float32)
    _, y_chunks = jax.lax.scan(
        jax.checkpoint(chunk_body),
        s0, (to_scan(xs_c), to_scan(b_c), to_scan(c_c), to_scan(dt_c),
             to_scan(da_c)))
    y = jnp.moveaxis(y_chunks, 0, 1).reshape(
        b, n_chunks * q, n_heads, ph)[:, :s]
    y = y.astype(xs.dtype) + xs * p["d_skip"].astype(
        xs.dtype)[None, None, :, None]
    y = y.reshape(b, s, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"])
    return (y @ p["w_out"]).astype(x.dtype)


def ssd_decode(p: Params, spec: SsmSpec, d_model: int, x: Array,
               cache: Dict[str, Array]) -> Tuple[Array, Dict[str, Array]]:
    """O(1) per-token SSD decode. x: (B, 1, D).

    cache: {"state": (B,H,N,P), "conv": (B,W-1,convdim), "len": (B,)}.
    """
    b = x.shape[0]
    d_in = spec.expand * d_model
    n_heads = d_in // spec.head_dim
    g, n, ph = spec.n_groups, spec.d_state, spec.head_dim

    proj = x @ p["w_in"]  # (B,1,dproj)
    z, xbc, dt = _ssm_split(p, spec, d_model, proj)
    # Causal conv against the rolling window.
    width = p["conv_w"].shape[0]
    window = jnp.concatenate([cache["conv"], xbc], axis=1)  # (B,W,conv)
    conv_out = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    xbc1 = jax.nn.silu(conv_out)[:, None, :]
    new_conv = window[:, 1:]

    xs = xbc1[..., :d_in].reshape(b, n_heads, ph)
    bmat = xbc1[..., d_in: d_in + g * n].reshape(b, g, n)
    cmat = xbc1[..., d_in + g * n:].reshape(b, g, n)
    heads_per_g = n_heads // g
    bmat = jnp.repeat(bmat, heads_per_g, axis=1)  # (B,H,N)
    cmat = jnp.repeat(cmat, heads_per_g, axis=1)

    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))  # (B,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    gate = jnp.exp(dt1 * a)  # (B,H)

    state32 = (cache["state"].astype(jnp.float32)
               * gate[..., None, None]
               + jnp.einsum("bhn,bhp->bhnp", bmat.astype(jnp.float32),
                            xs.astype(jnp.float32) * dt1[..., None]))
    state = state32.astype(cache["state"].dtype)
    y = jnp.einsum("bhn,bhnp->bhp", cmat.astype(jnp.float32), state32)
    y = y.astype(xs.dtype) + xs * p["d_skip"].astype(xs.dtype)[None, :, None]
    y = y.reshape(b, 1, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"])
    return (y @ p["w_out"]).astype(x.dtype), {
        "state": state, "conv": new_conv, "len": cache["len"] + 1}


def init_ssm_cache(spec: SsmSpec, d_model: int, batch: int, dtype,
                   ) -> Dict[str, Array]:
    d_in = spec.expand * d_model
    n_heads = d_in // spec.head_dim
    conv_dim = d_in + 2 * spec.n_groups * spec.d_state
    return {
        "state": jnp.zeros((batch, n_heads, spec.d_state, spec.head_dim),
                           dtype),
        "conv": jnp.zeros((batch, spec.conv_width - 1, conv_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }
