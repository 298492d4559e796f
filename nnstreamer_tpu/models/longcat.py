"""Decoder of shortcut-connected double blocks: two latent attentions
and two dense MLPs a layer, and beside them one expert layer whose
result skips the second attention and MLP before it is added; a softmax
router whose last ``zero_expert_num`` outputs are identity experts (the
LongCat-Flash architecture: its ``config.json`` names every size used
here).

A layer, for one sequence ``h0`` [S, d] (prefill, the expanded form):

    a1 = h0 + MLA_0(RMSNorm(h0))
    x1 = RMSNorm(a1)
    m  = MoE(x1)                  # the shortcut: read by the last line
    h1 = a1 + FFN_0(x1)
    a2 = h1 + MLA_1(RMSNorm(h1))
    x2 = RMSNorm(a2)
    h2 = a2 + FFN_1(x2) + m

* **MLA** (``models/latent.py``, the parts ``models/glm_dsa.py`` calls
  too): ``c_q = RMSNorm(x W_qa) * sqrt(d / q_lora_rank)``, ``c_kv =
  RMSNorm(.) * sqrt(d / kv_lora_rank)`` (``mla_scale_q_lora`` /
  ``mla_scale_kv_lora``; the roped key part unscaled), RoPE on
  interleaved pairs, scores over ``sqrt(nope + rope)``, every key ``s
  <= t``: no selection.
* **FFN**: ``(silu(x W_1) * (x W_3)) W_2``, ``ffn_hidden_size`` wide.
* **MoE.** ``p = softmax(x W_c)`` float32 over all ``n_routed_experts
  + zero_expert_num`` outputs; the ``moe_topk`` largest of ``p + b``
  are chosen (the bias moves the choice, not the weight); ``g_e =
  routed_scaling_factor * p_e``, not renormalised. ``MoE(x) = sum over
  chosen e held here of g_e SwiGLU_e(x) + (sum over chosen e >=
  n_routed_experts of g_e) x``: an identity expert costs no product.
  The layer is given ``(n_routed_experts, held_first, held_count)``,
  routes over the whole router and computes its own experts' part
  (``ops/grouped.py``) and the identity part, which every chip computes
  alike; what the experts of other chips would add is not stood in for.

Where ``MoE`` runs between ``x1`` and the last line is the compiler's:
the program states the dependency and no order. Scope names: ``embed``,
``block/attn`` (both attentions), ``block/mlp`` (both dense MLPs and the
norms before them), ``block/moe/route``, ``block/moe/zero``,
``block/moe/experts``, ``lm_head``.

Zoo entry ``zoo://longcat?...``: int32 token frame ``[S]`` -> three
tensors, ``last_logits`` float32 ``[V]``, ``logprobs`` float32 ``[S]``
(log-softmax of token t+1 at position t; 0 at S-1) and ``expert_load``
int32 ``[layers, held + 1]`` (token-expert pairs each held expert
served, and in the last column the pairs the identity experts took).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.grouped import group_by_expert, grouped_swiglu
from . import latent
from .latent import (BLOCK_Q, EXPERT_TILE, causal_attention_out, mla_qkv,
                     swiglu)
from .transformer import rmsnorm
from .zoo import register_model


@dataclasses.dataclass(frozen=True)
class LongCatConfig:
    """Field names are the published ``config.json`` keys.
    ``n_routed_experts`` counts the real experts of the router, which is
    ``n_routed_experts + zero_expert_num`` wide; ``held_first`` /
    ``held_count`` say which of the real ones this chip holds (0 held =
    all of them). ``vocab_size`` is the slice of the vocabulary held
    here."""
    vocab_size: int = 64
    hidden_size: int = 64
    num_layers: int = 2
    num_attention_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 12
    qk_rope_head_dim: int = 4
    v_head_dim: int = 16
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    ffn_hidden_size: int = 128
    expert_ffn_hidden_size: int = 32
    n_routed_experts: int = 24
    zero_expert_num: int = 8
    zero_expert_type: str = "identity"
    moe_topk: int = 4
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    held_first: int = 0
    held_count: int = 0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "LongCatConfig":
        """From a ``config.json`` dict; ``share``: ``held_first``,
        ``held_count`` and ``dtype``."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{**{k: v for k, v in hf.items() if k in names},
                      **share})

    @property
    def held(self) -> int:
        return self.held_count or self.n_routed_experts

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    def __post_init__(self):
        if self.held_first < 0 or \
                self.held_first + self.held > self.n_routed_experts:
            raise ValueError("held experts lie outside the real experts")
        if self.zero_expert_num and self.zero_expert_type != "identity":
            raise ValueError(f"zero_expert_type {self.zero_expert_type!r}: "
                             "only identity experts are built")
        if self.num_layers < 1 or self.moe_topk > self.router_width:
            raise ValueError("longcat needs a layer and a router at least "
                             "moe_topk wide")


def init_params(cfg: LongCatConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``: a layer is two ``sub`` layers
    (``attn_norm``, ``attn``, ``ffn_norm``, ``mlp``) and one ``moe``
    (``gate``, ``bias``, ``experts``). The router's bias is small and
    not zero, so that it changes choices, and its classifier sharp
    enough that the chosen scores carry weight."""
    return jax.jit(_init_params, static_argnums=0)(cfg, key)


def _init_params(cfg: LongCatConfig, key):
    dt, d, h = cfg.dtype, cfg.hidden_size, cfg.num_attention_heads
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    counter = itertools.count()

    def dense(*shape, fan_in=None, gain=1.0):
        k = jax.random.fold_in(key, next(counter))
        scale = gain * (fan_in or shape[-2]) ** -0.5
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def ones(n):
        return jnp.ones((n,), dt)

    def ffn(width, *lead):
        return {"w1": dense(*lead, d, width), "w3": dense(*lead, d, width),
                "w2": dense(*lead, width, d)}

    def sub():
        return {
            "attn_norm": ones(d),
            "attn": {
                "wq_a": dense(d, cfg.q_lora_rank),
                "q_norm": ones(cfg.q_lora_rank),
                "wq_b": dense(cfg.q_lora_rank, h * (nope + rp)),
                "wkv_a": dense(d, cfg.kv_lora_rank + rp),
                "kv_norm": ones(cfg.kv_lora_rank),
                "wkv_b": dense(cfg.kv_lora_rank, h * (nope + vd)),
                "wo": dense(h * vd, d)},
            "ffn_norm": ones(d),
            "mlp": ffn(cfg.ffn_hidden_size)}

    layers = [{
        "sub": [sub(), sub()],
        "moe": {
            "gate": dense(d, cfg.router_width, gain=2.0),
            "bias": dense(cfg.router_width, fan_in=1,
                          gain=0.25 / cfg.router_width),
            "experts": ffn(cfg.expert_ffn_hidden_size, cfg.held)},
    } for _ in range(cfg.num_layers)]
    return {"embed": dense(cfg.vocab_size, d, fan_in=d),
            "head": dense(d, cfg.vocab_size), "norm_f": ones(d),
            "layers": layers}


def attend(h, sub, cfg: LongCatConfig):
    """One attention sublayer for one sequence ``h`` [S, d]."""
    positions = jnp.arange(h.shape[0], dtype=jnp.int32)
    with jax.named_scope("block/attn"):
        x = rmsnorm(h, sub["attn_norm"], cfg.rms_norm_eps)
        _, q, k, v = mla_qkv(x, sub["attn"], positions, cfg,
                             q_scale=cfg.q_scale, kv_scale=cfg.kv_scale)
    return h + causal_attention_out(
        q, k, v, sub["attn"]["wo"], block_q=BLOCK_Q, scope="block/attn",
        scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)


def route(x, moe, cfg: LongCatConfig):
    """``x`` [T, d] -> ``(choice int32 [T, top], weight float32 [T,
    top])`` over the whole router, identity experts included. The bias
    moves the choice and not the weight; the chosen weights are the
    softmax's own, times ``routed_scaling_factor``, not renormalised."""
    p = jax.nn.softmax(jnp.dot(x, moe["gate"],
                               preferred_element_type=jnp.float32), axis=-1)
    _, choice = jax.lax.top_k(p + moe["bias"].astype(jnp.float32),
                              cfg.moe_topk)
    weight = jnp.take_along_axis(p, choice, axis=-1) \
        * cfg.routed_scaling_factor
    return choice.astype(jnp.int32), weight


def moe(x, m, cfg: LongCatConfig):
    """The expert layer over normed tokens ``x`` [T, d] -> ``(out
    float32 [T, d], load int32 [held + 1])``: this chip's routed
    experts' part plus the identity experts' (``x`` times the sum of a
    token's identity weights: no product), and the pairs each held
    expert served with the identity experts' pairs last."""
    with jax.named_scope("block/moe/route"):
        choice, weight = route(x, m, cfg)
        order, counts = group_by_expert(choice, cfg.held_first, cfg.held)
    with jax.named_scope("block/moe/zero"):
        zero = choice >= cfg.n_routed_experts
        out = x.astype(jnp.float32) * jnp.sum(
            jnp.where(zero, weight, 0.0), -1, keepdims=True)
        load = jnp.concatenate([counts, jnp.sum(zero, dtype=jnp.int32)[None]])
    with jax.named_scope("block/moe/experts"):
        e = m["experts"]
        out = out + grouped_swiglu(x, order, counts, weight, e["w1"],
                                   e["w3"], e["w2"], tile=EXPERT_TILE)
    return out, load


def block(h, layer, cfg: LongCatConfig):
    """One double block over sequences ``h`` [B, S, d] -> ``(h',
    load)``; the expert layer takes every sequence's tokens together."""
    b, s, d = h.shape
    first, second = layer["sub"]

    def attended(h, sub):
        return jnp.stack([attend(h[j], sub, cfg) for j in range(b)]
                         ).reshape(b * s, d)

    a1 = attended(h, first)
    with jax.named_scope("block/mlp"):
        x1 = rmsnorm(a1, first["ffn_norm"], cfg.rms_norm_eps)
    shortcut, load = moe(x1, layer["moe"], cfg)
    with jax.named_scope("block/mlp"):
        h1 = a1 + swiglu(x1, first["mlp"]).astype(a1.dtype)
    a2 = attended(h1.reshape(b, s, d), second)
    with jax.named_scope("block/mlp"):
        x2 = rmsnorm(a2, second["ffn_norm"], cfg.rms_norm_eps)
        h2 = a2 + (swiglu(x2, second["mlp"]) + shortcut).astype(a2.dtype)
    return h2.reshape(b, s, d), load


def forward(params, tokens, cfg: LongCatConfig):
    """``tokens`` int32 [B, S] -> ``(last_logits float32 [B, V],
    logprobs float32 [B, S], expert_load int32 [layers, held + 1])``;
    the load is summed over the batch."""
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
    loads = []
    for layer in params["layers"]:
        h, load = block(h, layer, cfg)
        loads.append(load)
    return latent.score(h, params, tokens, cfg.rms_norm_eps) \
        + (jnp.stack(loads),)


def frame_model(cfg: LongCatConfig, seq: int):
    """``(apply_fn, in_info, out_info)`` for ``tensor_filter
    framework=jax``: one int32 ``[seq]`` token frame a buffer in, the
    three tensors of :func:`forward` out."""
    return latent.frame_model(forward, cfg, seq,
                              f"{cfg.held + 1}:{cfg.num_layers}")


@register_model("longcat")
def _build_longcat(seq: str = "64", seed: str = "0", dtype: str = "bfloat16",
                   **sizes: str):
    """``zoo://longcat?seq=64&held_first=4&held_count=4&...``: any field
    of :class:`LongCatConfig` by its name; the defaults are a tiny model
    whose router chooses real and identity experts alike."""
    cfg = latent.config_from_options(LongCatConfig, "longcat", dtype, sizes)
    params = init_params(cfg, jax.random.PRNGKey(int(seed)))
    apply_fn, in_info, out_info = frame_model(cfg, int(seq))
    return apply_fn, params, in_info, out_info
