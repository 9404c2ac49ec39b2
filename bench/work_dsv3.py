"""Operations of the DeepSeek-V3 front end's work, from the
configuration's shapes (``bench/configs/dsv3-frontend-7l-1024x1024.json``).

Counted from what the algorithm needs, whatever kernel does it: each
multiply-add is 2 operations; causal attention counts the keys each
query may see; the held experts count the routed slots that reached
them (the layer computes those and no padding).
"""
from __future__ import annotations


def _attn_proj(cfg: dict) -> int:
    """Multiply-adds of MLA's projections, a token."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    lora = cfg["kv_lora_rank"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (lora + cfg["qk_rope_head_dim"])
            + lora * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_macs(cfg: dict) -> int:
    """Multiply-adds of one routed (or shared) expert, a token."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def row_flops(cfg: dict, tokens: int, held_slots_per_token: float) -> float:
    """Operations of one row of ``tokens`` tokens end to end: every
    layer's attention (projections and the causal scores and values),
    the dense FFNs, each MoE layer's router, shared expert and held
    experts at ``held_slots_per_token`` (averaged over the MoE layers),
    then the pooled row's encode and the flat packed search."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    seen = tokens * (tokens + 1) / 2          # keys seen, summed
    attn = tokens * _attn_proj(cfg) + seen * h * (qk + cfg["v_head_dim"])
    ffn = tokens * 3 * d * cfg["intermediate_size"]
    moe_ffn = tokens * (d * cfg["n_routed_experts"]
                        + cfg["n_shared_experts"] * expert_macs(cfg)
                        + held_slots_per_token * expert_macs(cfg))
    macs = (cfg["num_hidden_layers"] * attn + dense * ffn + moe * moe_ffn
            + d * cfg["dim"] + cfg["dim"] * cfg["columns"])
    return 2.0 * macs
