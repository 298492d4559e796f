"""Operations and bytes the configurations' forward passes need, from
their shapes alone. Re-derived here (``bench.py``'s ``_chained_invoke_fps``
has the same shape arithmetic for the ViT); ``--selftest`` checks them
against hand-worked counts."""
from __future__ import annotations


def vit_tokens(cfg: dict) -> int:
    return (cfg["image_size"] // cfg["patch_size"]) ** 2


def vit_param_count(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d   # attn, mlp, 2 LN
    patch = cfg["patch_size"] ** 2 * cfg["num_channels"] * d + d
    pos = vit_tokens(cfg) * d
    head = d * cfg["num_classes"] + cfg["num_classes"]
    return cfg["num_hidden_layers"] * layer + patch + pos + 2 * d + head


def vit_flops_per_frame(cfg: dict) -> float:
    """Multiply-adds x 2 of one frame's forward pass: patch embedding,
    the blocks' six matmuls and two attention products, the head."""
    d, f, t = cfg["hidden_size"], cfg["intermediate_size"], vit_tokens(cfg)
    matmul = 4 * d * d + 2 * d * f                  # weights a block
    attn = 2 * t * d                                # QK^T and PV, a token
    block = 2 * t * (matmul + attn)
    patch = 2 * t * cfg["patch_size"] ** 2 * cfg["num_channels"] * d
    head = 2 * d * cfg["num_classes"]
    return float(cfg["num_hidden_layers"] * block + patch + head)


def gpt_layer_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 3 * d * f


def gpt_param_count(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (gpt_layer_params(cfg) + 2 * d)
            + 2 * cfg["vocab_size"] * d + d)


def gpt_kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    return 2 * cfg["hidden_size"] * dtype_bytes * cfg["num_hidden_layers"]


def gpt_flops_per_token(cfg: dict, context: float, with_head: bool = True
                        ) -> float:
    """One token's forward at ``context`` attended positions. A prefill
    token of a prompt of n tokens attends (n + 1) / 2 on average and only
    the last one goes through the head."""
    d = cfg["hidden_size"]
    flops = 2.0 * cfg["num_hidden_layers"] * (
        gpt_layer_params(cfg) + 2 * context * d)
    if with_head:
        flops += 2.0 * d * cfg["vocab_size"]
    return flops


def gpt_decode_step_bytes(cfg: dict, live_tokens: float,
                          dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must read: every layer's weights and
    the head once (the embedding is a gather of a few rows), and the live
    keys and values of the lanes it serves."""
    d = cfg["hidden_size"]
    weights = (cfg["num_hidden_layers"] * gpt_layer_params(cfg)
               + d * cfg["vocab_size"]) * dtype_bytes
    return float(weights + live_tokens * gpt_kv_bytes_per_token(
        cfg, dtype_bytes))
