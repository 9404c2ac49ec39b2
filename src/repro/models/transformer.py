"""Model assembly: embeddings -> scanned block groups -> head(s).

One ``TransformerLM`` implementation serves all ten assigned
architectures; the ``ModelConfig.blocks`` schedule decides what each group
of layers computes. Parameters of a group are *stacked* along a leading
``repeat`` axis and the forward pass scans over them (one trace per
group), keeping 96-layer dry-run compiles tractable and matching
production practice (MaxText does the same).

Public surface:
  init_params(key, cfg)                  -> (params, axes)
  forward(params, cfg, batch)            -> logits [, aux]
  loss_fn(params, cfg, batch)            -> scalar loss, metrics
  init_cache(cfg, batch, max_len, dtype) -> decode caches
  decode_step(params, cfg, batch, cache) -> logits, cache
  pooled_features(params, cfg, tokens)   -> pooled hidden states, aux
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import BlockSpec, ModelConfig
from repro.models.sharding import shard_act

Array = jax.Array
Params = Dict[str, Any]

VIT_DIM = 1024  # stub ViT feature width for vision_patches frontends


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key: Array, cfg: ModelConfig, b: BlockSpec,
                ) -> Tuple[Params, Params]:
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    p: Params = {}
    a: Params = {}
    p["ln1"], a["ln1"] = L.init_rms_norm(cfg.d_model, dt)
    has_ffn = not (b.ffn.kind == "dense" and b.ffn.d_ff == 0)
    if has_ffn:
        p["ln2"], a["ln2"] = L.init_rms_norm(cfg.d_model, dt)
    if b.mixer in ("attn", "hybrid"):
        if b.attn.kind == "gqa":
            p["attn"], a["attn"] = L.init_gqa(ks[0], cfg.d_model, b.attn, dt)
        else:
            p["attn"], a["attn"] = L.init_mla(ks[0], cfg.d_model, b.attn, dt)
    if b.mixer in ("ssm", "hybrid"):
        p["ssm"], a["ssm"] = L.init_ssm(ks[1], cfg.d_model, b.ssm, dt)
    if b.cross_attn:
        p["ln_x"], a["ln_x"] = L.init_rms_norm(cfg.d_model, dt)
        p["xattn"], a["xattn"] = L.init_cross_attn(
            ks[2], cfg.d_model, b.attn, dt)
    if b.ffn.kind == "moe":
        p["ffn"], a["ffn"] = L.init_moe_ffn(ks[3], cfg.d_model, b.ffn, dt)
    elif has_ffn:
        p["ffn"], a["ffn"] = L.init_dense_ffn(ks[3], cfg.d_model, b.ffn, dt)
    return p, a


def _stack_group(key: Array, cfg: ModelConfig, b: BlockSpec,
                 ) -> Tuple[Params, Params]:
    keys = jax.random.split(key, b.repeat)
    if L.is_abstract():
        p0, axes = _init_layer(keys[0], cfg, b)
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((b.repeat,) + tuple(s.shape),
                                           s.dtype), p0)
    else:
        def init_i(k):
            return _init_layer(k, cfg, b)[0]

        stacked = jax.vmap(init_i)(keys)
        axes = _init_layer_axes(cfg, b)
    # Prepend the scan ("layers") axis to every logical-axes tuple.
    axes = jax.tree.map(lambda ax: (None,) + ax, axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    return stacked, axes


def _init_layer_axes(cfg: ModelConfig, b: BlockSpec) -> Params:
    """Axes tree only (no array allocation)."""
    with L.abstract_init():
        _, axes = _init_layer(jax.random.key(0), cfg, b)
    return axes


def init_params(key: Array, cfg: ModelConfig) -> Tuple[Params, Params]:
    """Returns (params, logical_axes) with identical tree structure."""
    dt = _dtype(cfg.param_dtype)
    n_groups = len(cfg.blocks)
    ks = jax.random.split(key, n_groups + 5)
    p: Params = {}
    a: Params = {}

    emb_std = 1.0 / math.sqrt(cfg.d_model)

    def _emb(key, shape):
        return L._maybe_sds(
            lambda: (jax.random.normal(key, shape) * emb_std).astype(dt),
            shape, dt)

    p["embed"] = _emb(ks[0], (cfg.padded_vocab, cfg.d_model))
    a["embed"] = ("vocab", "embed")
    if not cfg.tie_embeddings:
        p["unembed"] = _emb(ks[1], (cfg.padded_vocab, cfg.d_model))
        a["unembed"] = ("vocab", "embed")
    if cfg.n_codebooks > 1:
        p["codebook_heads"] = _emb(
            ks[2], (cfg.n_codebooks - 1, cfg.padded_vocab, cfg.d_model))
        a["codebook_heads"] = (None, "vocab", "embed")
    if cfg.frontend == "vision_patches":
        p["patch_proj"] = L._dense_init(ks[3], (VIT_DIM, cfg.d_model), dt)
        a["patch_proj"] = (None, "embed")

    groups = []
    groups_axes = []
    for gi, b in enumerate(cfg.blocks):
        gp, ga = _stack_group(ks[5 + gi], cfg, b)
        groups.append(gp)
        groups_axes.append(ga)
    p["groups"] = groups
    a["groups"] = groups_axes

    p["ln_f"], a["ln_f"] = L.init_rms_norm(cfg.d_model, dt)

    if cfg.mtp_depth:
        mtp_spec = cfg.blocks[-1]
        mp, ma = _init_layer(ks[4], cfg, mtp_spec)
        p["mtp"] = {"block": mp,
                    "proj": L._dense_init(
                        jax.random.fold_in(ks[4], 1),
                        (2 * cfg.d_model, cfg.d_model), dt),
                    "ln": L.init_rms_norm(cfg.d_model, dt)[0]}
        a["mtp"] = {"block": ma, "proj": (None, "embed"),
                    "ln": ("embed",)}
    return p, a


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer_forward(cfg: ModelConfig, b: BlockSpec, lp: Params, x: Array,
                   positions: Array, cond: Optional[Array],
                   ) -> Tuple[Array, Dict[str, Array]]:
    aux: Dict[str, Array] = {}
    h = L.rms_norm(x, lp["ln1"], cfg.rms_eps)
    mix = None
    if b.mixer in ("attn", "hybrid"):
        if b.attn.kind == "gqa":
            att = L.gqa_forward(lp["attn"], b.attn, h, positions)
        else:
            att = L.mla_forward(lp["attn"], b.attn, h, positions,
                                cfg.rms_eps)
        mix = att
    if b.mixer in ("ssm", "hybrid"):
        ss = L.ssd_forward(lp["ssm"], b.ssm, cfg.d_model, h)
        mix = ss if mix is None else 0.5 * (mix + ss)  # hymba fusion
    x = x + mix
    if b.cross_attn and cond is not None:
        hx = L.rms_norm(x, lp["ln_x"], cfg.rms_eps)
        x = x + L.cross_attn_forward(lp["xattn"], b.attn, hx, cond)
    if "ffn" in lp:
        h2 = L.rms_norm(x, lp["ln2"], cfg.rms_eps)
        if b.ffn.kind == "dense":
            y = L.dense_ffn(lp["ffn"], b.ffn, h2)
        else:
            y, aux = L.moe_ffn(lp["ffn"], b.ffn, h2)
        x = x + y
    x = shard_act(x, ("batch", "seq", "act_embed"))
    return x, aux


def _group_forward(cfg: ModelConfig, b: BlockSpec, gp: Params, x: Array,
                   positions: Array, cond: Optional[Array],
                   ) -> Tuple[Array, Dict[str, Array]]:
    """Scan the group's layers; aux entries stacked per layer."""
    def body(carry, lp):
        y, aux = _layer_forward(cfg, b, lp, carry, positions, cond)
        return y, aux

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.lax.scan(body, x, gp)


def embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, Array],
                 ) -> Tuple[Array, Array, Optional[Array]]:
    """Returns (hidden, positions, cond)."""
    dt = _dtype(cfg.activation_dtype)
    if cfg.frontend == "audio_frames":
        x = batch["frame_embeds"].astype(dt)
        b, s = x.shape[:2]
        positions = jnp.arange(s)[None, :].repeat(b, 0)
        cond = batch.get("cond_embeds")
        cond = cond.astype(dt) if cond is not None else None
        return x, positions, cond
    tok = batch["tokens"]
    x = params["embed"][tok].astype(dt)
    if cfg.frontend == "vision_patches":
        patches = batch["patch_feats"].astype(dt) @ params["patch_proj"]
        x = jnp.concatenate([patches.astype(dt), x], axis=1)
    b, s = x.shape[:2]
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    return x, positions, None


def _head(params: Params, cfg: ModelConfig, h: Array) -> Array:
    w = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,vd->bsv", h, w.astype(h.dtype))
    logits = shard_act(logits, ("batch", "seq", "act_vocab"))
    if cfg.n_codebooks > 1:
        extra = jnp.einsum("bsd,cvd->bscv", h,
                           params["codebook_heads"].astype(h.dtype))
        logits = jnp.concatenate([logits[:, :, None, :], extra], axis=2)
    return logits


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Array],
            ) -> Tuple[Array, Dict[str, Array]]:
    """Full-sequence forward. Returns (logits, aux).

    logits: (B, S, V) or (B, S, n_codebooks, V) for audio.
    """
    x, positions, cond = embed_inputs(params, cfg, batch)
    x = shard_act(x, ("batch", "seq", "act_embed"))
    aux_total: Dict[str, Array] = {}
    for gi, (b, gp) in enumerate(zip(cfg.blocks, params["groups"])):
        x, auxs = _group_forward(cfg, b, gp, x, positions, cond)
        # Sum per-layer aux across the group.
        aux = {k: jnp.sum(v, axis=0) for k, v in auxs.items()}
        for k, v in aux.items():
            if k == "expert_counts":
                # Kept per group (groups may differ in expert count) for
                # the aux-free router-bias update (DeepSeek-V3);
                # layer-summed within the group.
                aux_total[f"expert_counts_g{gi}"] = v
            else:
                aux_total[k] = aux_total.get(k, 0.0) + v
    h = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
    logits = _head(params, cfg, h)
    aux_total["final_hidden"] = h
    return logits, aux_total


def pooled_features(params: Params, cfg: ModelConfig, tokens: Array,
                    ) -> Tuple[Array, Dict[str, Array]]:
    """Prefill as a frozen feature extractor: embed the (B, S) token
    rows, run the layer groups, apply the final RMSNorm and take each
    row's mean over its positions. No output head, no MTP module.

    Returns ((B, d_model) pooled hidden states in the activation dtype
    (bfloat16 for a bfloat16 model), aux): for
    held-range MoE layers (``FfnSpec.n_held``) aux holds
    ``held_counts`` (B, MoE layers, n_held) int32, the row's slots
    routed to each held expert in each layer, and ``dropped`` (B,)
    int32, the row's held slots left uncomputed over all layers.
    """
    x, positions, _ = embed_inputs(params, cfg, {"tokens": tokens})
    counts, dropped = [], []
    for b, gp in zip(cfg.blocks, params["groups"]):
        x, auxs = _group_forward(cfg, b, gp, x, positions, None)
        if "held_counts" in auxs:              # (layers, B, n_held)
            counts.append(jnp.swapaxes(auxs["held_counts"], 0, 1))
            dropped.append(jnp.sum(auxs["dropped"], axis=0))
    h = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
    pooled = jnp.mean(h.astype(jnp.float32), axis=1).astype(h.dtype)
    aux = {}
    if counts:
        aux = {"held_counts": jnp.concatenate(counts, axis=1),
               "dropped": sum(dropped)}
    return pooled, aux


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def _xent(logits: Array, targets: Array, mask: Optional[Array]) -> Array:
    logits32 = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, targets[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, Array],
            ) -> Tuple[Array, Dict[str, Array]]:
    logits, aux = forward(params, cfg, batch)
    h = aux.pop("final_hidden")

    if cfg.frontend == "audio_frames" and cfg.n_codebooks > 1:
        loss = _xent(logits, batch["targets"], None)  # (B,S,CB,V) vs (B,S,CB)
    elif cfg.frontend == "vision_patches":
        # Text-only loss; patch positions are context.
        n_p = batch["patch_feats"].shape[1]
        loss = _xent(logits[:, n_p:], batch["targets"], None)
    else:
        loss = _xent(logits, batch["targets"], None)

    metrics = {"lm_loss": loss}
    if "lb_loss" in aux:
        lb = 0.01 * aux["lb_loss"]
        loss = loss + lb
        metrics["lb_loss"] = lb
    for k, v in aux.items():
        if k.startswith("expert_counts_g"):
            metrics[k] = v

    if cfg.mtp_depth and cfg.frontend == "none":
        # DeepSeek-V3 MTP: predict t+2 from [h_i ; emb(t_{i+1})].
        emb_next = params["embed"][batch["targets"]].astype(h.dtype)
        hin = jnp.concatenate([h, emb_next], axis=-1) @ params["mtp"]["proj"]
        positions = jnp.arange(h.shape[1])[None, :].repeat(h.shape[0], 0)
        hm, _ = _layer_forward(cfg, cfg.blocks[-1], params["mtp"]["block"],
                               hin, positions, None)
        hm = L.rms_norm(hm, params["mtp"]["ln"], cfg.rms_eps)
        mtp_logits = _head(params, cfg, hm)[:, :-1]
        mtp_targets = batch["targets"][:, 1:]
        mtp = 0.3 * _xent(mtp_logits, mtp_targets, None)
        loss = loss + mtp
        metrics["mtp_loss"] = mtp

    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype_name: Optional[str] = None) -> list:
    """Per-group stacked decode caches.

    Windowed attention layers allocate ring buffers of min(window, S);
    global layers allocate the full horizon; SSM layers are O(1).
    """
    dt = _dtype(dtype_name or cfg.activation_dtype)
    caches = []
    for b in cfg.blocks:
        def stack(tree, repeat):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (repeat,) + x.shape), tree)

        entry: Dict[str, Any] = {}
        if b.mixer in ("attn", "hybrid"):
            if b.attn.kind == "gqa":
                one = L.init_gqa_cache(b.attn, batch, max_len, dt,
                                       quant=cfg.kv_cache_quant)
            else:
                one = L.init_mla_cache(b.attn, batch, max_len, dt)
            entry["attn"] = stack(one, b.repeat)
        if b.mixer in ("ssm", "hybrid"):
            one_s = L.init_ssm_cache(b.ssm, cfg.d_model, batch, dt)
            entry["ssm"] = stack(one_s, b.repeat)
        caches.append(entry)
    return caches


def _layer_decode(cfg: ModelConfig, b: BlockSpec, lp: Params, x: Array,
                  cache: Dict[str, Any], cond: Optional[Array],
                  ) -> Tuple[Array, Dict[str, Any]]:
    new_cache: Dict[str, Any] = {}
    h = L.rms_norm(x, lp["ln1"], cfg.rms_eps)
    mix = None
    if b.mixer in ("attn", "hybrid"):
        if b.attn.kind == "gqa":
            if cfg.kv_cache_quant:
                att, new_cache["attn"] = L.gqa_decode_quant(
                    lp["attn"], b.attn, h, cache["attn"])
            else:
                att, new_cache["attn"] = L.gqa_decode(
                    lp["attn"], b.attn, h, cache["attn"],
                    seq_parallel=cfg.seq_parallel_decode)
        else:
            att, new_cache["attn"] = L.mla_decode(lp["attn"], b.attn, h,
                                                  cache["attn"], cfg.rms_eps)
        mix = att
    if b.mixer in ("ssm", "hybrid"):
        ss, new_cache["ssm"] = L.ssd_decode(lp["ssm"], b.ssm, cfg.d_model,
                                            h, cache["ssm"])
        mix = ss if mix is None else 0.5 * (mix + ss)
    x = x + mix
    if b.cross_attn and cond is not None:
        hx = L.rms_norm(x, lp["ln_x"], cfg.rms_eps)
        x = x + L.cross_attn_forward(lp["xattn"], b.attn, hx, cond)
    if "ffn" in lp:
        h2 = L.rms_norm(x, lp["ln2"], cfg.rms_eps)
        if b.ffn.kind == "dense":
            y = L.dense_ffn(lp["ffn"], b.ffn, h2)
        else:
            y, _ = L.moe_ffn(lp["ffn"], b.ffn, h2)
        x = x + y
    return x, new_cache


def decode_step(params: Params, cfg: ModelConfig, batch: Dict[str, Array],
                caches: list) -> Tuple[Array, list]:
    """One decode step for the whole stack.

    batch: {"tokens": (B, 1)} (or {"frame_embeds": (B, 1, D)} for audio;
    vlm decodes text tokens). caches: output of init_cache, with "len"
    already advanced past any prefill.

    Returns (logits, new_caches); logits (B, V) or (B, CB, V).
    """
    dt = _dtype(cfg.activation_dtype)
    if cfg.frontend == "audio_frames":
        x = batch["frame_embeds"].astype(dt)
        cond = batch.get("cond_embeds")
        cond = cond.astype(dt) if cond is not None else None
    else:
        x = params["embed"][batch["tokens"]].astype(dt)
        cond = None

    new_caches = []
    for b, gp, gc in zip(cfg.blocks, params["groups"], caches):
        def body(carry, scanned):
            lp, lc = scanned
            y, nc = _layer_decode(cfg, b, lp, carry, lc, cond)
            return y, nc

        x, nc = jax.lax.scan(body, x, (gp, gc))
        new_caches.append(nc)
    h = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
    logits = _head(params, cfg, h)
    return logits[:, 0], new_caches
