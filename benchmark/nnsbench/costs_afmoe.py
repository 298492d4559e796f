"""Operations and bytes the ``afmoe`` configurations' scoring pass
needs, from their shapes alone: the useful work of the published
mathematics, whatever implements it. A sliding layer's attention counts
the pairs its window keeps (``sum_t min(t + 1, sliding_window)``), a full
layer's the causal pairs, neither the tiles a kernel visits; an expert
layer counts the router, the shared expert and the share of a token's
chosen experts that this chip holds (all of them where it holds the
whole router). :func:`attention_floor_s` is the attention kernel's
roofline a sequence, :func:`grouped_floor_s` the routed experts'
grouped product's. A test holds each to hand-worked counts."""
from __future__ import annotations

# the pair counts are the glm family's
from nnsbench.costs_glm import causal_pairs, selected_pairs

SLIDING = "sliding_attention"
BYTES = 2       # bfloat16 operands


def attention_params(cfg: dict) -> int:
    """The five matrices: q, gate and output over all heads, k and v
    over the key/value heads (the norms' vectors left out)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * (3 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_experts_per_token(cfg: dict) -> float:
    """Of a token's chosen experts, how many are held here when the
    choice is even over the router."""
    return cfg["num_experts_per_tok"] / cfg["expert_parallel"]


def kept_pairs(cfg: dict, s: int, kind: str) -> int:
    """Query-key pairs a layer of ``kind`` keeps over a sequence."""
    if kind == SLIDING:
        return selected_pairs(s, cfg["sliding_window"])
    return causal_pairs(s)


def attention_flops(cfg: dict, s: int, kind: str) -> float:
    """``q.k`` and ``p.v`` of the kept pairs, every query head."""
    return 4.0 * kept_pairs(cfg, s, kind) * cfg["num_attention_heads"] \
        * cfg["head_dim"]


def layer_flops(cfg: dict, s: int, kind: str, moe: bool) -> float:
    """Multiply-adds x 2 of one layer over one sequence of ``s``."""
    per_token = attention_params(cfg)
    if moe:
        per_token += (cfg["hidden_size"] * cfg["num_experts"]
                      + (cfg["num_shared_experts"]
                         + held_experts_per_token(cfg)) * expert_params(cfg))
    else:
        per_token += 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return 2.0 * s * per_token + attention_flops(cfg, s, kind)


def sequence_flops(cfg: dict, s: int) -> float:
    """One sequence's scoring pass: every layer by its kind, and the
    head at every position (the log-probabilities need each position's
    logits)."""
    layers = sum(layer_flops(cfg, s, kind, i >= cfg["num_dense_layers"])
                 for i, kind in enumerate(cfg["layer_types"]))
    return layers + 2.0 * s * cfg["hidden_size"] * cfg["vocab_size"]


def attention_bytes(cfg: dict, s: int) -> float:
    """q read and o written a query head, k and v read once a
    key/value head."""
    return float(BYTES * s * cfg["head_dim"] * 2 * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"]))


def attention_layer_floor_s(cfg: dict, s: int, kind: str, peaks: dict) -> float:
    """The least seconds the chip could take over one layer's attention
    of ``kind``: the larger of its operations over the peak rate and its
    bytes over the memory's."""
    return max(attention_flops(cfg, s, kind) / peaks["flops_bf16"],
               attention_bytes(cfg, s) / peaks["hbm_bytes_per_s"])


def attention_floor_s(cfg: dict, s: int, peaks: dict) -> float:
    """The same over one sequence: every layer by its kind. The name a
    reader asks a family's cost module for
    (``metrics/kernel.nns_masked_attention.roofline_pct.py``)."""
    return sum(attention_layer_floor_s(cfg, s, kind, peaks)
               for kind in cfg["layer_types"])


def held_experts(cfg: dict) -> int:
    """Routed experts of a layer whose weights this chip holds."""
    return cfg["num_experts"] // cfg["expert_parallel"]


def grouped_flops(cfg: dict, s: int) -> float:
    """The routed experts' three products over the pairs served here,
    one expert layer."""
    return 2.0 * s * held_experts_per_token(cfg) * expert_params(cfg)


def grouped_bytes(cfg: dict, s: int) -> float:
    """What any form of the routed experts' product has to move, one
    expert layer: each held expert's three matrices once, a row of the
    stream's width in and one out for each pair served (what lies
    between the three products can stay on the chip)."""
    pairs = s * held_experts_per_token(cfg)
    return BYTES * (held_experts(cfg) * expert_params(cfg)
                    + 2.0 * pairs * cfg["hidden_size"])


def grouped_floor_s(cfg: dict, s: int, peaks: dict) -> float:
    """The least seconds the chip could take over one sequence's routed
    experts: every expert layer's larger of operations over the peak
    rate and bytes over the memory's."""
    layers = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    return layers * max(grouped_flops(cfg, s) / peaks["flops_bf16"],
                        grouped_bytes(cfg, s) / peaks["hbm_bytes_per_s"])
