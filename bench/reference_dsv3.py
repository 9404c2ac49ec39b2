"""Plain float32 reference of the DeepSeek-V3 front end and its MEMHD head.

Independent of the program: it imports nothing of ``repro`` and reads
only what the benchmark made from the seed (the weights, in the
parameter tree the program is given, the token rows, the projection and
the AM). Every contraction runs in float32 under
``jax.default_matmul_precision("highest")``. The configuration is the
published ``config.json`` (huggingface.co/deepseek-ai/DeepSeek-V3) as cut
in ``bench/configs/dsv3-frontend-7l-1024x1024.json``.

What it computes, per token row (DeepSeek-V3 technical report,
arXiv:2412.19437, and the model's published modelling code):

* embedding, then ``num_hidden_layers`` pre-norm blocks: RMSNorm
  (eps ``rms_norm_eps``) -> MLA -> residual, RMSNorm -> FFN -> residual;
  the first ``first_k_dense_replace`` FFNs dense (SwiGLU), the rest MoE;
* MLA: q = W_qb RMSNorm(W_qa x), split 128 nope + 64 rope; c_kv =
  RMSNorm of the first 512 columns of W_kva x, k_rope its last 64, shared
  by all heads; k_nope = c_kv W_kb, v = c_kv W_vb; YaRN RoPE on q_rope and
  k_rope; causal softmax attention with scale mscale^2 / sqrt(192),
  mscale = 0.1 ln(factor) + 1;
* MoE gate (``noaux_tc``): scores = sigmoid(x W_r) (float32); choice by
  scores + bias; each of the 8 groups scored by the sum of its top-2
  biased scores; the 4 best groups kept; the top 8 experts within them;
  weights = the chosen unbiased scores, normalised, times 2.5;
* the cut: the MoE output is the shared expert plus the held experts'
  part, sum over held chosen experts e of w_e * SwiGLU_e(x); what the
  other experts add is left out, as on the device that holds this share;
* head: final RMSNorm, mean over the row's positions, rounded to
  bfloat16 (the configuration's feature rows); query bits
  sign(features @ M) >= 0; class = owner of the AM column of highest
  bipolar similarity (the lowest index among equals).

Departures from the published description, all of them layout:

* RoPE pairs are (i, i + 32) of the 64 rope columns (the half-split
  layout). The published checkpoint stores interleaved pairs (2i, 2i+1)
  and its code de-interleaves them before ``rotate_half``: the same map
  up to a fixed permutation of the rope columns of W_qb and W_kva, which
  seeded weights do not see.
* RMSNorm weights are stored as (weight - 1): the scale is 1 + stored.
* W_kb and W_vb are the two halves of the published ``kv_b_proj``, kept
  apart; the router bias is ``e_score_correction_bias``.

Controls (``Variant``): the same forward with its matmul operands
rounded to float8 e4m3 (per-tensor scaled to the format's range, the
nearest precision below the configuration's bfloat16), with group-limited
routing off, without the routed scaling factor, or without YaRN's
mscale on the softmax scale. Each has to come out not correct.
"""
from __future__ import annotations

import dataclasses
import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 128  # queries a score block: (4, 128, 128, 2048) float32, 0.5 GB


@partial(jax.tree_util.register_dataclass,
         data_fields=["fp8", "group_routing", "routed_scale", "mscale"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Variant:
    """The sound forward (all defaults) or one of the controls; its
    switches are traced, so that every control runs the one compiled
    forward."""

    fp8: bool = False
    group_routing: bool = True
    routed_scale: bool = True
    mscale: bool = True


SOUND = Variant()
CONTROLS = {"fp8": Variant(fp8=True),
            "no_group_routing": Variant(group_routing=False),
            "no_routed_scale": Variant(routed_scale=False),
            "no_mscale": Variant(mscale=False)}


def _fp8(a):
    """Round to float8 e4m3, scaled per tensor to the format's range."""
    amax = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    s = 448.0 / amax
    return (a * s).astype(jnp.float8_e4m3fn).astype(F32) / s


def _mm(v: Variant, spec: str, a, b):
    a = jnp.where(v.fp8, _fp8(a), a)
    b = jnp.where(v.fp8, _fp8(b), b)
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict):
    """The 32 rotary inverse frequencies under YaRN (``rope_scaling``)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    y = cfg["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(cfg: dict, v: Variant = SOUND):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    y = cfg["rope_scaling"]
    m = _mscale(y["factor"], y["mscale_all_dim"]) if y.get(
        "mscale_all_dim") else 1.0
    return jnp.where(v.mscale, scale * m * m, scale)


def _rope(x, pos, inv_freq, amp):
    ang = pos[:, None] * inv_freq[None, :]          # (S, dim/2)
    cos, sin = amp * jnp.cos(ang), amp * jnp.sin(ang)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, cfg, x, v: Variant):
    eps = cfg["rms_norm_eps"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lora = cfg["kv_lora_rank"]
    s = x.shape[1]
    pos = jnp.arange(s, dtype=F32)
    y = cfg["rope_scaling"]
    inv_freq = jnp.asarray(yarn_inv_freq(cfg), F32)
    amp = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                     y["mscale_all_dim"])
    q = _mm(v, "bsr,rhk->bshk",
            _rms(_mm(v, "bsd,dr->bsr", x, p["wq_a"]), p["q_norm"], eps),
            p["wq_b"])
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos, inv_freq, amp)], -1)
    kv = _mm(v, "bsd,dr->bsr", x, p["wkv_a"])
    c = _rms(kv[..., :lora], p["kv_norm"], eps)
    k_rope = _rope(kv[..., None, lora:], pos, inv_freq, amp)
    k_nope = _mm(v, "bsr,rhk->bshk", c, p["wk_b"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rd,))], -1)
    val = _mm(v, "bsr,rhk->bshk", c, p["wv_b"])
    scale = softmax_scale(cfg, v)
    outs = []
    for start in range(0, s, Q_BLOCK):
        stop = min(start + Q_BLOCK, s)
        sc = _mm(v, "bqhd,bkhd->bhqk", q[:, start:stop], k[:, :stop]) * scale
        causal = (start + jnp.arange(stop - start))[:, None] >= jnp.arange(
            stop)[None, :]
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(_mm(v, "bhqk,bkhd->bqhd", w, val[:, :stop]))
    return _mm(v, "bshk,hkd->bsd", jnp.concatenate(outs, 1), p["wo"])


def _swiglu(v, x, wg, wu, wd):
    g = _mm(v, "td,df->tf", x, wg)
    return _mm(v, "tf,fd->td", jax.nn.silu(g) * _mm(v, "td,df->tf", x, wu),
               wd)


def route(cfg: dict, logits, bias, v: Variant = SOUND):
    """(T, k) chosen experts and their weights (``noaux_tc``)."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    ng, kg = cfg["n_group"], cfg["topk_group"]
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias
    if ng > 1:
        t = choice.shape[0]
        top2 = jax.lax.top_k(choice.reshape(t, ng, e // ng), 2)[0]
        _, groups = jax.lax.top_k(top2.sum(-1), kg)
        keep = jnp.zeros((t, ng), bool).at[
            jnp.arange(t)[:, None], groups].set(True)
        keep = jnp.repeat(keep, e // ng, axis=1) | ~jnp.asarray(
            v.group_routing)
        choice = jnp.where(keep, choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * jnp.where(v.routed_scale, cfg["routed_scaling_factor"],
                              1.0)


def _moe(p, cfg, x, v: Variant):
    """Shared expert + the held experts' part; (B, S, D) and the row's
    slots routed to each held expert (B, n_held)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    idx, w = route(cfg, _mm(v, "td,de->te", xt, p["router"]),
                   p["router_bias"], v)
    y = _swiglu(v, xt, p["ws_gate"], p["ws_up"], p["ws_down"])
    e0, nh = cfg["experts_held_start"], cfg["experts_held"]
    counts = []
    for j in range(nh):
        chosen = idx == e0 + j                                  # (T, k)
        wj = jnp.sum(jnp.where(chosen, w, 0.0), axis=1)
        y = y + wj[:, None] * _swiglu(v, xt, p["w_gate"][j], p["w_up"][j],
                                      p["w_down"][j])
        counts.append(chosen.reshape(b, -1).sum(1))
    return y.reshape(b, s, d), jnp.stack(counts, -1)


@lru_cache(maxsize=None)
def _layer_fn(cfg_json: str, moe: bool):
    cfg = json.loads(cfg_json)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def layer(group, i, x, v):
        # One layer's weights upcast to float32, the rest left as they are.
        p = jax.tree.map(lambda a: a[i].astype(F32), group)
        with jax.default_matmul_precision("highest"):
            x = x + _attention(p["attn"], cfg, _rms(x, p["ln1"], eps), v)
            h = _rms(x, p["ln2"], eps)
            if moe:
                y, counts = _moe(p["ffn"], cfg, h, v)
            else:
                f = p["ffn"]
                b, s, d = h.shape
                y = _swiglu(v, h.reshape(b * s, d), f["w_in"], f["w_up"],
                            f["w_out"]).reshape(b, s, d)
                counts = jnp.zeros((b, 0), jnp.int32)
        return x + y, counts

    return layer


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@jax.jit
def search(bits, am_bits, owners):
    """(n, D) bool query bits -> (n,) the owner of the AM column of
    highest bipolar similarity (the lowest index among equals)."""
    with jax.default_matmul_precision("highest"):
        q = jnp.where(bits, 1.0, -1.0)
        sims = q @ jnp.where(am_bits, 1.0, -1.0).T
    return owners[jnp.argmax(sims, axis=-1)]


@partial(jax.jit, static_argnames=("feature_dtype",))
def _head(ln_f, x, eps, proj, am_bits, owners, feature_dtype):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, ln_f.astype(F32), eps)
        feats = jnp.mean(h, axis=1).astype(feature_dtype).astype(F32)
        bits = feats @ proj >= 0
    return search(bits, am_bits, owners), bits, feats


def forward(params, cfg: dict, tokens, proj, am_bits, owners,
            v: Variant = SOUND, feature_dtype=jnp.bfloat16):
    """One block of token rows (B, S) -> (classes (B,), query bits
    (B, D) bool, held slot counts (B, MoE layers, n_held), features
    (B, hidden) float32, as rounded to ``feature_dtype``)."""
    dense = cfg["first_k_dense_replace"]
    key = json.dumps(cfg, sort_keys=True)
    layers = [_layer_fn(key, False), _layer_fn(key, True)]
    x = _embed(params["embed"], tokens)
    counts = []
    for moe, group in enumerate(params["groups"]):
        n = dense if not moe else cfg["num_hidden_layers"] - dense
        for i in range(n):
            x, c = layers[moe](group, i, x, v)
            if moe:
                counts.append(c)
    cls, bits, feats = _head(params["ln_f"], x, cfg["rms_norm_eps"], proj,
                             am_bits, owners, feature_dtype)
    return cls, bits, jnp.stack(counts, 1), feats


def answers(params, cfg: dict, tokens, proj, am_bits, owners, *,
            block: int = 4, v: Variant = SOUND, feature_dtype=jnp.bfloat16):
    """``forward`` over (n, S) rows in blocks of ``block`` rows, on the
    host: (classes (n,), bits (n, D) bool, counts (n, L, n_held))."""
    outs = [forward(params, cfg, tokens[i:i + block], proj, am_bits, owners,
                    v, feature_dtype)[:3]
            for i in range(0, tokens.shape[0], block)]
    return tuple(np.concatenate([np.asarray(o[j]) for o in outs])
                 for j in range(3))
