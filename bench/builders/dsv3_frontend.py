"""DeepSeek-V3's prefill as a frozen front end (one EP rank's share of
its layers) feeding MEMHD's packed AM, deployed as the program's
``"frontend"`` backend and served through ``serve_batches``.

The configuration holds the published ``config.json`` numbers under
their own keys (``bench/configs/dsv3-frontend-7l-1024x1024.json``); this
builder maps them onto the program's model schema, makes every weight on
the device from the seed in the parameter tree the program is given, and
hands the same arrays to the plain reference (``bench/reference_dsv3.py``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference_dsv3
from bench.systems import AM, POOL, PROJ, System

WEIGHTS = 6  # key stream of the LM's weights
ROUTER_BIAS_SIGMA = 0.02


def lm_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration's numbers."""
    from repro.models.config import (
        AttnSpec, BlockSpec, FfnSpec, ModelConfig, YarnSpec,
    )
    y = cfg["rope_scaling"]
    attn = AttnSpec(
        kind="mla", n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnSpec(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])))
    dense = FfnSpec(kind="dense", d_ff=cfg["intermediate_size"],
                    activation="silu_glu")
    moe = FfnSpec(
        kind="moe", d_ff=cfg["intermediate_size"], activation="silu_glu",
        n_experts=cfg["n_routed_experts"], n_shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"], d_ff_expert=cfg[
            "moe_intermediate_size"], router="sigmoid",
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        n_held=cfg["experts_held"], held_start=cfg["experts_held_start"])
    k = cfg["first_k_dense_replace"]
    return ModelConfig(
        name="deepseek-v3-frontend", d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"],
        blocks=(BlockSpec(repeat=k, attn=attn, ffn=dense),
                BlockSpec(repeat=cfg["num_hidden_layers"] - k, attn=attn,
                          ffn=moe)),
        # No output head is run, so none is made (an untied one would
        # be a second 1.85 GB table).
        tie_embeddings=True,
        rms_eps=cfg["rms_norm_eps"], param_dtype=cfg["dtype"],
        activation_dtype=cfg["dtype"], remat=False)


def _fan_in(name: str, body: tuple) -> int:
    """A weight's contracted size: its rows, the heads x head dim of the
    attention output, an expert matrix's rows after the expert axis."""
    if name == "wo":
        return body[0] * body[1]
    if len(body) == 3 and name in ("w_gate", "w_up", "w_down"):
        return body[1]
    return body[0]


@partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, *, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(key, lm_cfg):
    """Every weight of ``lm_cfg`` from ``key``, on the device, in the
    program's parameter tree, in its ``param_dtype``: matrices
    N(0, 1/fan-in), the embedding N(0, 1), norm scales 0 (a scale of 1),
    the router in float32, its bias N(0, ROUTER_BIAS_SIGMA^2)."""
    from repro.models import layers, transformer
    dtype = jnp.dtype(lm_cfg.param_dtype)
    with layers.abstract_init():
        shapes, _ = transformer.init_params(jax.random.key(0), lm_cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for i, (path, sds) in enumerate(paths):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        shape = tuple(sds.shape)
        stacked = any(getattr(p, "idx", None) is not None for p in path)
        body = shape[1:] if stacked else shape
        if "norm" in name or name.startswith("ln"):
            leaves.append(jnp.zeros(shape, dtype))
        elif name == "router_bias":
            leaves.append(_normal(k, shape=shape, dtype=jnp.float32,
                                  std=ROUTER_BIAS_SIGMA))
        elif name == "embed":
            leaves.append(_normal(k, shape=shape, dtype=dtype, std=1.0))
        else:
            dt = jnp.float32 if name == "router" else dtype
            leaves.append(_normal(k, shape=shape, dtype=dt,
                                  std=1.0 / math.sqrt(_fan_in(name, body))))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@partial(jax.jit, static_argnames=("n", "tokens", "vocab"))
def token_rows(key, *, n: int, tokens: int, vocab: int, zipf_s: float):
    """(n, tokens) int32 ids: Zipf(s) ranks over a seeded permutation of
    the vocabulary (rank r with probability proportional to r^-s)."""
    kp, ku = jax.random.split(key)
    w = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -zipf_s
    cdf = jnp.cumsum(w) / jnp.sum(w)
    ranks = jnp.searchsorted(cdf, jax.random.uniform(ku, (n, tokens)))
    perm = jax.random.permutation(kp, vocab)
    return perm[jnp.minimum(ranks, vocab - 1)].astype(jnp.int32)


def build(cfg: dict, seed: int) -> System:
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    from repro.core import am as am_lib
    from repro.deploy import get_backend
    from repro.models import transformer

    # A program without the front end fails here, before any weight.
    get_backend("frontend")
    transformer.pooled_features  # noqa: B018

    lm_cfg = lm_config(cfg)
    f, d, c = cfg["hidden_size"], cfg["dim"], cfg["columns"]
    params = make_params(gen.seed_key(seed, WEIGHTS), lm_cfg)
    proj = gen.projection(gen.seed_key(seed, PROJ), features=f, dim=d)
    am_bits = jax.random.bernoulli(gen.seed_key(seed, AM), 0.5, (c, d))
    owners = jnp.arange(c, dtype=jnp.int32) % cfg["classes"]
    binary = jnp.where(am_bits, 1.0, -1.0).astype(jnp.float32)
    model = MemhdModel(
        {"projection": proj}, am_lib.make_am_state(binary, owners),
        EncoderConfig(kind="projection", features=f, dim=d),
        MemhdConfig(dim=d, columns=c, classes=cfg["classes"]))
    artifact = model.deploy(target=cfg["backend"], lm_params=params,
                            lm_cfg=lm_cfg)

    def rows(n, *, tokens, zipf_s):
        return np.asarray(token_rows(gen.seed_key(seed, POOL), n=n,
                                     tokens=tokens, vocab=cfg["vocab_size"],
                                     zipf_s=zipf_s))

    def answers(tokens, variant=reference_dsv3.SOUND):
        return reference_dsv3.answers(params, cfg, jnp.asarray(tokens), proj,
                                      am_bits, owners, v=variant,
                                      feature_dtype=jnp.dtype(cfg["dtype"]))

    system = System(cfg, model, artifact, proj, rows, answers)
    # The reference's search alone, over query bits the timed path gave.
    system.search = lambda bits: np.asarray(reference_dsv3.search(
        jnp.asarray(bits), am_bits, owners))
    return system
