"""Operations the ``glm_dsa`` configurations' scoring pass needs, from
their shapes alone: the useful work of the published mathematics,
whatever implements it. Attention counts the pairs the indexer selects
(``sum_t min(t + 1, index_topk)``), not the causal square a masked
implementation computes; the indexer counts every causal pair; the
routed experts count the expected share of a token's chosen experts
that this chip holds. A test holds each to hand-worked counts."""
from __future__ import annotations


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def selected_pairs(s: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` over positions 0..s-1."""
    full = min(s, topk)
    return full * (full + 1) // 2 + (s - full) * topk


def attention_params(cfg: dict) -> int:
    """MLA's five matrices (the norms' vectors left out)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def indexer_params(cfg: dict) -> int:
    d, hi, di = cfg["hidden_size"], cfg["index_n_heads"], \
        cfg["index_head_dim"]
    return cfg["q_lora_rank"] * hi * di + d * di + d * hi


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_flops(cfg: dict, s: int, moe: bool) -> float:
    """Multiply-adds x 2 of one layer over one sequence of ``s``."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_token = attention_params(cfg) + indexer_params(cfg)
    if moe:
        held_share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
            / cfg["n_routed_experts_total"]
        per_token += (cfg["hidden_size"] * cfg["n_routed_experts_total"]
                      + (cfg["n_shared_experts"] + held_share)
                      * expert_params(cfg))
    else:
        per_token += 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    index = causal_pairs(s) * cfg["index_n_heads"] * cfg["index_head_dim"]
    attend = selected_pairs(s, cfg["index_topk"]) * h * (
        qk + cfg["v_head_dim"])
    return 2.0 * (s * per_token + index + attend)


def sequence_flops(cfg: dict, s: int) -> float:
    """One sequence's scoring pass: every layer, and the head at every
    position (the log-probabilities need each position's logits)."""
    dense = cfg["first_k_dense_replace"]
    layers = dense * layer_flops(cfg, s, False) \
        + (cfg["num_hidden_layers"] - dense) * layer_flops(cfg, s, True)
    return layers + 2.0 * s * cfg["hidden_size"] * cfg["vocab_size"]
