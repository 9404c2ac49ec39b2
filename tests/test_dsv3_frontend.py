"""The DeepSeek-V3 front end against its plain reference, on the CPU.

Small widths, seeded weights, float32 throughout. The reference is the
benchmark's (``bench/reference_dsv3.py``): plain ``jax.numpy`` that
imports nothing of the program. Covered: the pooled features and the
query bits through the ``"frontend"`` artifact, group-limited routing,
the held-range MoE layer (its shares add up to the uncut layer, and a
batch routed wholly onto held experts drops nothing), and YaRN.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference_dsv3 as R
from bench.builders import dsv3_frontend as B
from repro.models import layers as L
from repro.models import transformer as T

CONFIG = (Path(__file__).resolve().parents[1] / "bench" / "configs"
          / "dsv3-frontend-7l-1024x1024.json")
F32 = jnp.float32


def small_cfg(**over):
    """The configuration file at small widths, all its mechanisms kept:
    16 experts in 4 groups (top-2 groups), top-4, 4 held from expert 4."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=16, experts_held=4, experts_held_start=4,
        n_group=4, topk_group=2, num_experts_per_tok=4,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, vocab_size=256, num_hidden_layers=3,
        first_k_dense_replace=1, dim=128, columns=64, classes=4,
        dtype="float32", rope_scaling=dict(cfg["rope_scaling"],
                          original_max_position_embeddings=16))
    cfg.update(over)
    return cfg


def f32_model(cfg, seed=3):
    lm = B.lm_config(cfg)
    return lm, B.make_params(jax.random.key(seed), lm)


def _moe_group(params):
    return params["groups"][1]


def test_pooled_features_and_query_bits_match_reference():
    from repro.core import EncoderConfig, MemhdConfig, MemhdModel
    from repro.core import am as am_lib
    from repro.deploy import deploy
    cfg = small_cfg()
    lm, params = f32_model(cfg)
    tokens = B.token_rows(jax.random.key(5), n=4, tokens=48,
                          vocab=cfg["vocab_size"], zipf_s=1.1)
    feats, aux = jax.jit(T.pooled_features, static_argnums=1)(
        params, lm, tokens)
    proj = jax.random.rademacher(jax.random.key(6), (64, 128), F32)
    am_bits = jax.random.bernoulli(jax.random.key(7), 0.5, (64, 128))
    owners = jnp.arange(64, dtype=jnp.int32) % 4
    cls, bits, counts, want = R.forward(params, cfg, tokens, proj, am_bits,
                                        owners, feature_dtype=F32)
    err = float(jnp.linalg.norm(feats - want) / jnp.linalg.norm(want))
    assert feats.dtype == F32 and err < 1e-5, err
    np.testing.assert_array_equal(np.asarray(aux["held_counts"]),
                                  np.asarray(counts))
    assert int(aux["held_counts"].sum()) > 0
    assert not np.asarray(aux["dropped"]).any()

    binary = jnp.where(am_bits, 1.0, -1.0)
    model = MemhdModel({"projection": proj},
                       am_lib.make_am_state(binary, owners),
                       EncoderConfig(kind="projection", features=64,
                                     dim=128),
                       MemhdConfig(dim=128, columns=64, classes=4))
    art = deploy(model, "frontend", lm_params=params, lm_cfg=lm)
    out = np.asarray(art.predict_features(tokens))
    lay = art.layout
    got = np.unpackbits(np.ascontiguousarray(
        out[:, lay["query"]].astype("<i4")).view(np.uint8), axis=-1,
        bitorder="little").astype(bool)
    np.testing.assert_array_equal(got, np.asarray(bits))
    np.testing.assert_array_equal(out[:, 0], np.asarray(cls))
    np.testing.assert_array_equal(
        out[:, lay["counts"]].reshape(4, 2, 4), np.asarray(counts))
    assert not out[:, lay["dropped"]].any()


def test_route_matches_reference_group_limited_choice():
    cfg = small_cfg()
    spec = B.lm_config(cfg).blocks[1].ffn
    logits = 2.0 * jax.random.normal(jax.random.key(1), (512, 16))
    bias = 0.1 * jax.random.normal(jax.random.key(2), (16,))
    _, w, idx = L._route(logits, spec, bias)
    want_idx, want_w = R.route(cfg, logits, bias)
    order, want_order = jnp.argsort(idx, 1), jnp.argsort(want_idx, 1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(idx), np.asarray(order), 1),
        np.take_along_axis(np.asarray(want_idx), np.asarray(want_order), 1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), np.asarray(order), 1),
        np.take_along_axis(np.asarray(want_w), np.asarray(want_order), 1),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(1)), 2.5, rtol=1e-5)
    # Every choice lies in the token's two best groups, and the limit
    # binds: without it some tokens would choose in a masked group.
    groups = np.asarray(idx) // 4
    assert (np.array([len(set(g)) for g in groups]) <= 2).all()
    free_idx, _ = R.route(cfg, logits, bias,
                          R.Variant(group_routing=False))
    assert (np.sort(np.asarray(free_idx), 1)
            != np.sort(np.asarray(idx), 1)).any(axis=1).mean() > 0.2


def _layer_input(cfg, t=2, s=24):
    return jax.random.normal(jax.random.key(11), (t, s, cfg["hidden_size"]))


def _moe_params(cfg, seed=4):
    _, params = f32_model(cfg, seed)
    return jax.tree.map(lambda a: a[0], _moe_group(params))["ffn"]


def test_held_shares_add_up_to_the_uncut_layer():
    """E = 16 over 4 shares: the shares' partial outputs, with the shared
    expert counted once, equal the reference's whole layer."""
    uncut = small_cfg(experts_held=16, experts_held_start=0)
    p = _moe_params(uncut)
    x = _layer_input(uncut)
    with jax.default_matmul_precision("highest"):
        want, want_counts = R._moe(p, uncut, x, R.SOUND)
        shared = R._swiglu(R.SOUND, x.reshape(-1, 64), p["ws_gate"],
                           p["ws_up"], p["ws_down"]).reshape(x.shape)
    total, counts = 0.0, []
    for share in range(4):
        spec = B.lm_config(small_cfg(experts_held=4,
                                     experts_held_start=4 * share)
                           ).blocks[1].ffn
        held = dict(p, **{k: p[k][4 * share:4 * share + 4]
                          for k in ("w_gate", "w_up", "w_down")})
        y, aux = jax.jit(L.moe_ffn, static_argnums=1)(held, spec, x)
        total = total + y
        counts.append(aux["held_counts"])
        assert not np.asarray(aux["dropped"]).any()
    total = total - 3 * shared
    err = float(jnp.linalg.norm(total - want) / jnp.linalg.norm(want))
    assert err < 1e-5, err
    np.testing.assert_array_equal(np.concatenate(counts, 1),
                                  np.asarray(want_counts))
    assert int(want_counts.sum()) == x.shape[0] * x.shape[1] * 4


@pytest.mark.parametrize("held_bias,tokens,chunk", [
    ([1], 24, None), ([0, 1, 2, 3], 24, None), ([0, 1, 2, 3], 64, 256)],
    ids=["one_expert", "all_held", "all_held_two_passes"])
def test_batch_routed_onto_held_experts_drops_nothing(held_bias, tokens,
                                                      chunk, monkeypatch):
    """Every token routed to the biased held experts: one of them takes a
    slot of every token, or all four take every slot; with 512 held slots
    and 256 a pass, the layer runs two passes."""
    if chunk:
        monkeypatch.setattr(L, "MOE_CHUNK", chunk)
    cfg = small_cfg()
    p = _moe_params(cfg)
    bias = jnp.zeros((16,)).at[4 + jnp.asarray(held_bias)].set(10.0)
    p = dict(p, router_bias=bias)
    x = _layer_input(cfg, s=tokens)
    spec = B.lm_config(cfg).blocks[1].ffn
    y, aux = jax.jit(L.moe_ffn, static_argnums=1)(p, spec, x)
    with jax.default_matmul_precision("highest"):
        want, want_counts = R._moe(p, cfg, x, R.SOUND)
    tokens = x.shape[1]
    counts = np.asarray(aux["held_counts"])
    np.testing.assert_array_equal(counts, np.asarray(want_counts))
    assert (counts[:, held_bias] == tokens).all()
    assert not np.asarray(aux["dropped"]).any()
    err = float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want))
    assert err < 1e-5, err


def test_yarn_frequencies_and_scale_match_reference():
    cfg = json.loads(CONFIG.read_text())
    spec = B.lm_config(cfg).blocks[0].attn
    got = L.rope_freqs(spec.qk_rope_dim, spec.rope_theta, spec.rope_scaling)
    np.testing.assert_allclose(np.asarray(got), R.yarn_inv_freq(cfg),
                               rtol=1e-6)
    # The fastest pairs keep their frequency, the slowest are divided by
    # the factor, and a ramp joins them.
    plain = L.rope_freqs(spec.qk_rope_dim, spec.rope_theta)
    ratio = np.asarray(plain / got)
    assert ratio[0] == pytest.approx(1.0)
    assert ratio[-1] == pytest.approx(40.0)
    assert (np.diff(ratio) >= -1e-6).all()
    mscale = 0.1 * np.log(40.0) + 1.0
    assert L.mla_scale(spec) == pytest.approx(mscale ** 2 / np.sqrt(192))
    assert L.mla_scale(spec) == pytest.approx(float(R.softmax_scale(cfg)))


def test_encode_pack_takes_bf16_rows_exactly():
    """bf16 feature rows x the bipolar projection: one pass with float32
    accumulation, every product exact, so the packed bits equal the
    float32 oracle's (the sums differ only in float32 rounding)."""
    from repro.kernels import ref
    from repro.kernels.encode_fused import encode_pack
    x = jax.random.normal(jax.random.key(8), (4, 7168)).astype(jnp.bfloat16)
    proj = jax.random.rademacher(jax.random.key(9), (7168, 1024), F32)
    got = encode_pack(x, proj.astype(jnp.bfloat16))
    want = ref.encode_pack(x.astype(F32), proj)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
