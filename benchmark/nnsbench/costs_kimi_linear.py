"""Operations and bytes the ``kimi_linear`` configurations' scoring pass
needs, from their shapes alone: the useful work of the published
mathematics, whatever implements it. A KDA layer counts its projections
(the low-rank gates' two matrices each, the convolutions' taps) and the
state recurrence as the token-by-token form states it (``k^T S``, the
rank-one update and ``S^T q`` at ``2 dk dv`` each and the decay at ``dk
dv``, a token and head), not the products a chunked form chooses; the
MLA layer counts the causal pairs; an expert layer the router, the
shared expert and the share of a token's chosen experts that this chip
holds. :func:`kda_floor_s` is the chunked recurrence's roofline a
sequence, :func:`attention_floor_s` the attention kernel's,
:func:`grouped_floor_s` the routed experts' grouped product's (the
names the kernels' readers ask a family's cost module for). A test
holds each to hand-worked counts."""
from __future__ import annotations

from nnsbench.costs_glm import causal_pairs

BYTES = 2       # bfloat16 operands
FLOAT = 4       # the log-decays and beta are float32


def kda_layers(cfg: dict) -> int:
    return len(cfg["linear_attn_config"]["kda_layers"])


def mla_layers(cfg: dict) -> int:
    return len(cfg["linear_attn_config"]["full_attn_layers"])


def kda_params(cfg: dict) -> int:
    """A KDA mixer's matrices: q, k, v and output over all heads, the
    two low-rank gates (decay and output) through the head's width,
    beta, and the three convolutions' taps (the norm's, ``A_log``'s and
    ``dt_bias``' vectors left out)."""
    d, hd = cfg["hidden_size"], cfg["kda_head_dim"]
    wide = cfg["kda_num_heads"] * hd
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    return (4 * d * wide + 2 * (d * hd + hd * wide)
            + d * cfg["kda_num_heads"] + 3 * taps * wide)


def kda_core_flops(cfg: dict, s: int) -> float:
    """The state recurrence of one KDA layer over ``s`` tokens: 7 ``dk
    dv`` a token and head (module docstring)."""
    return 7.0 * s * cfg["kda_num_heads"] * cfg["kda_head_dim"] ** 2


def kda_core_bytes(cfg: dict, s: int) -> float:
    """What any form of the recurrence has to move, one layer: q, k and
    v in and o out once at the stream's width, a float32 log-decay a
    key channel and a float32 beta a head in (the state stays on the
    chip)."""
    hd = cfg["kda_head_dim"]
    return float(s * cfg["kda_num_heads"] * (
        BYTES * 4 * hd + FLOAT * (hd + 1)))


def kda_floor_s(cfg: dict, s: int, peaks: dict) -> float:
    """The least seconds the chip could take over one sequence's
    recurrences: every KDA layer's larger of operations over the peak
    rate and bytes over the memory's (the memory's, at these sizes)."""
    return kda_layers(cfg) * max(
        kda_core_flops(cfg, s) / peaks["flops_bf16"],
        kda_core_bytes(cfg, s) / peaks["hbm_bytes_per_s"])


def mla_params(cfg: dict) -> int:
    """The MLA mixer's four matrices, no query latent (the norm's
    vector left out)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return (d * h * (nope + rope) + d * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * h * (nope + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def attention_flops(cfg: dict, s: int) -> float:
    """``q.k`` and ``p.v`` of the causal pairs, every head, one MLA
    layer."""
    return 2.0 * causal_pairs(s) * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def attention_bytes(cfg: dict, s: int) -> float:
    """q and each head's k read, each head's v read and o written,
    once, one MLA layer."""
    return float(BYTES * s * cfg["num_attention_heads"] * 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]))


def attention_floor_s(cfg: dict, s: int, peaks: dict) -> float:
    """The least seconds the chip could take over one sequence's
    softmax attention: every MLA layer's larger of operations over the
    peak rate and bytes over the memory's."""
    return mla_layers(cfg) * max(
        attention_flops(cfg, s) / peaks["flops_bf16"],
        attention_bytes(cfg, s) / peaks["hbm_bytes_per_s"])


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_experts_per_token(cfg: dict) -> float:
    """Of a token's chosen experts, how many are held here when the
    choice is even over the router."""
    return cfg["num_experts_per_token"] * cfg["num_experts"] \
        / cfg["num_experts_total"]


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def ffn_flops(cfg: dict, s: int, moe: bool) -> float:
    """The second half of one layer over ``s`` tokens."""
    d = cfg["hidden_size"]
    if moe:
        per_token = d * cfg["num_experts_total"] + (
            cfg["num_shared_experts"] + held_experts_per_token(cfg)
        ) * expert_params(cfg)
    else:
        per_token = 3 * d * cfg["intermediate_size"]
    return 2.0 * s * per_token


def sequence_flops(cfg: dict, s: int) -> float:
    """One sequence's scoring pass: every layer's mixer by its kind and
    its second half, and the head at every position (the
    log-probabilities need each position's logits)."""
    mixers = kda_layers(cfg) * (2.0 * s * kda_params(cfg)
                                + kda_core_flops(cfg, s)) \
        + mla_layers(cfg) * (2.0 * s * mla_params(cfg)
                             + attention_flops(cfg, s))
    dense = cfg["first_k_dense_replace"]
    ffns = dense * ffn_flops(cfg, s, False) \
        + moe_layers(cfg) * ffn_flops(cfg, s, True)
    return mixers + ffns + 2.0 * s * cfg["hidden_size"] * cfg["vocab_size"]


def grouped_flops(cfg: dict, s: int) -> float:
    """The routed experts' three products over the pairs served here,
    one expert layer."""
    return 2.0 * s * held_experts_per_token(cfg) * expert_params(cfg)


def grouped_bytes(cfg: dict, s: int) -> float:
    """What any form of the routed experts' product has to move, one
    expert layer: each held expert's three matrices once, a row of the
    stream's width in and one out for each pair served."""
    pairs = s * held_experts_per_token(cfg)
    return BYTES * (cfg["num_experts"] * expert_params(cfg)
                    + 2.0 * pairs * cfg["hidden_size"])


def grouped_floor_s(cfg: dict, s: int, peaks: dict) -> float:
    """The least seconds the chip could take over one sequence's routed
    experts: every expert layer's larger of operations over the peak
    rate and bytes over the memory's."""
    return moe_layers(cfg) * max(
        grouped_flops(cfg, s) / peaks["flops_bf16"],
        grouped_bytes(cfg, s) / peaks["hbm_bytes_per_s"])
