"""Operations the ``longcat`` configurations' scoring pass needs, from
their shapes alone: the useful work of the published mathematics,
whatever implements it. Attention counts the causal pairs (``s (s + 1)
/ 2``), not padded lanes or the tiles a kernel visits; a layer is two
attentions, two dense MLPs and one expert layer; the routed experts
count the expected share of a token's chosen experts that this chip
holds (``moe_topk x held / router width``, an even choice); an identity
expert costs nothing. A test holds each to hand-worked counts."""
from __future__ import annotations

# MLA's five matrices and the causal pair count are the glm family's
from nnsbench.costs_glm import attention_params, causal_pairs  # noqa: F401


def dense_params(cfg: dict) -> int:
    """One dense MLP's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_width(cfg: dict) -> int:
    return cfg["n_routed_experts_total"] + cfg["zero_expert_num"]


def held_experts_per_token(cfg: dict) -> float:
    """Of a token's ``moe_topk`` choices, how many fall on the real
    experts held here when the choice is even over the router."""
    return cfg["moe_topk"] * cfg["n_routed_experts"] / router_width(cfg)


def layer_flops(cfg: dict, s: int) -> float:
    """Multiply-adds x 2 of one double layer over one sequence of ``s``."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_token = (2 * attention_params(cfg) + 2 * dense_params(cfg)
                 + cfg["hidden_size"] * router_width(cfg)
                 + held_experts_per_token(cfg) * expert_params(cfg))
    attend = 2 * causal_pairs(s) * h * (qk + cfg["v_head_dim"])
    return 2.0 * (s * per_token + attend)


def sequence_flops(cfg: dict, s: int) -> float:
    """One sequence's scoring pass: every layer, and the head at every
    position (the log-probabilities need each position's logits)."""
    return cfg["num_layers"] * layer_flops(cfg, s) \
        + 2.0 * s * cfg["hidden_size"] * cfg["vocab_size"]
