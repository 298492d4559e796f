"""Operations and bytes the ``dots3_note`` configurations' scoring pass
needs, from their shapes alone: the useful work of the published
mathematics, whatever implements it. A full layer's attention counts
the pairs the indexer selects (``sum_t min(t + 1, index_topk)``) and its
indexer every causal pair, a sliding layer's the pairs its window keeps
(``sum_t min(t + 1, sliding_window_size)``), neither the tiles a kernel
visits; each kind at its own heads and head sizes (the ``swa_*`` keys
for the sliding one). An expert layer counts the router, the shared
expert and the share of a token's chosen experts that this chip holds.
:func:`attention_floor_s` is the attention kernel's roofline a
sequence. A test holds each to hand-worked counts."""
from __future__ import annotations

# the pair counts and the indexer's matrices are the glm family's
from nnsbench.costs_glm import causal_pairs, indexer_params, selected_pairs

SLIDING = "sliding_attention"
BYTES = 2       # bfloat16 operands


def kind_sizes(cfg: dict, kind: str) -> dict:
    """A layer kind's attention sizes by the unprefixed names."""
    pre = "swa_" if kind == SLIDING else ""
    return {k: cfg[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}


def attention_params(cfg: dict, kind: str) -> int:
    """MLA's five matrices and the headwise gate (the norms' vectors
    left out)."""
    a, d = kind_sizes(cfg, kind), cfg["hidden_size"]
    h, rq, rkv = a["num_attention_heads"], a["q_lora_rank"], \
        a["kv_lora_rank"]
    nope, rope, v = a["qk_nope_head_dim"], a["qk_rope_head_dim"], \
        a["v_head_dim"]
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + v) + h * v * d + d * h)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def kept_pairs(cfg: dict, s: int, kind: str) -> int:
    """Query-key pairs a layer of ``kind`` keeps over a sequence."""
    if kind == SLIDING:
        return selected_pairs(s, cfg["sliding_window_size"])
    return selected_pairs(s, cfg["index_topk"])


def attention_flops(cfg: dict, s: int, kind: str) -> float:
    """``q.k`` and ``p.v`` of the kept pairs, every head."""
    a = kind_sizes(cfg, kind)
    return 2.0 * kept_pairs(cfg, s, kind) * a["num_attention_heads"] * (
        a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"])


def layer_flops(cfg: dict, s: int, kind: str, moe: bool) -> float:
    """Multiply-adds x 2 of one layer over one sequence of ``s``."""
    per_token = attention_params(cfg, kind)
    index = 0
    if kind != SLIDING:
        per_token += indexer_params(cfg)
        index = causal_pairs(s) * cfg["index_n_heads"] \
            * cfg["index_head_dim"]
    if moe:
        held_share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
            / cfg["n_routed_experts_total"]
        per_token += (cfg["hidden_size"] * cfg["n_routed_experts_total"]
                      + (cfg["n_shared_experts"] + held_share)
                      * expert_params(cfg))
    else:
        per_token += 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return 2.0 * (s * per_token + index) + attention_flops(cfg, s, kind)


def sequence_flops(cfg: dict, s: int) -> float:
    """One sequence's scoring pass: every layer by its kind, and the
    head at every position (the log-probabilities need each position's
    logits)."""
    layers = sum(layer_flops(cfg, s, kind, i >= cfg["first_k_dense_replace"])
                 for i, kind in enumerate(cfg["layer_types"]))
    return layers + 2.0 * s * cfg["hidden_size"] * cfg["vocab_size"]


def attention_bytes(cfg: dict, s: int, kind: str) -> float:
    """q, k, v read and o written once, every head at its published
    sizes (the expanded form has no shared key/value heads)."""
    a = kind_sizes(cfg, kind)
    return float(BYTES * s * a["num_attention_heads"] * 2 * (
        a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"]))


def attention_floor_s(cfg: dict, s: int, peaks: dict) -> float:
    """The least seconds the chip could take over one sequence's
    attention: every layer by its kind, the larger of its operations
    over the peak rate and its bytes over the memory's. The name a
    reader asks a family's cost module for
    (``metrics/kernel.nns_masked_attention.roofline_pct.py``)."""
    return sum(max(attention_flops(cfg, s, kind) / peaks["flops_bf16"],
                   attention_bytes(cfg, s, kind) / peaks["hbm_bytes_per_s"])
               for kind in cfg["layer_types"])
