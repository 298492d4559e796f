"""Decoder with latent attention, a learned sparse-attention indexer and a
sigmoid-routed expert layer that is told which experts it holds (the
``glm_moe_dsa`` architecture: GLM-5's ``config.json`` names every size
used here).

A layer, for one sequence ``x`` [S, d] (prefill, the expanded form; the
absorbed form belongs to a decode path and is not built here):

* **MLA.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` per head
  ``(nope | rope)``, RoPE (interleaved pairs) on the rope part;
  ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, RoPE on ``k_r``,
  one for all heads; ``[k_nope | v] = c_kv W_kvb`` per head; scores
  ``q.[k_nope | k_r] / sqrt(nope + rope)`` over keys ``s <= t`` that the
  indexer selected, float32 softmax, ``o = (P v) W_o``.
* **Indexer.** ``q_I = c_q W_Iq`` (heads x dim), ``k_I = LayerNorm(x
  W_Ik)`` (one a token), RoPE on the first ``rope`` of each; ``I[t,s] =
  sum_h w[t,h] ReLU(q_I[t,h].k_I[s])`` with ``w = x W_Iw / sqrt(heads x
  dim)``, float32; ``sel(t)`` = the ``index_topk`` largest ``I[t, s<=t]``,
  all of them while ``t < index_topk``, ties to the lower index
  (``ops/sparse_attention.py``).
* **Router** (``latent.sigmoid_route``: ``noaux_tc``, one group). ``s
  = sigmoid(x W_g)`` float32
  over the whole router; the ``top`` largest of ``s + b`` are chosen;
  weights ``s_e / (sum_chosen s + 1e-20) x routed_scaling_factor``, the
  sum over every chosen expert, held or not. The layer's output is ``x
  + shared(x) + sum_{e chosen and held} w_e expert_e(x)``: it is given
  ``(n_routed_experts, held_first, held_count)``, routes over all and
  computes its own experts' part (``ops/grouped.py``); what the experts
  of other chips would add is not stood in for.

The first ``first_k_dense_replace`` layers have a dense SwiGLU MLP in the
expert layer's place. The latent projections, the rotations, the SwiGLU
and the attention half are ``models/latent.py``'s, which
``models/longcat.py`` calls too. Scope names: ``embed``, ``block/attn``,
``block/indexer`` (``block/indexer/select`` inside), ``block/moe/route``,
``block/moe/shared``, ``block/moe/experts``, ``block/mlp``, ``lm_head``.

Zoo entry ``zoo://glm_dsa?...``: int32 token frame ``[S]`` -> three
tensors, ``last_logits`` float32 ``[V]``, ``logprobs`` float32 ``[S]``
(log-softmax of token t+1 at position t; 0 at S-1) and ``expert_load``
int32 ``[moe layers, held]`` (token-expert pairs each held expert
served). The multi-token-prediction layer does nothing on a scoring
pass and is not loaded.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.grouped import group_by_expert, grouped_swiglu
from ..ops.sparse_attention import topk_mask
from . import latent
from .latent import (BLOCK_Q, EXPERT_TILE, _mm, causal_attention_out,
                     mla_qkv, rope_interleaved, sigmoid_route, swiglu)
from .transformer import rmsnorm
from .zoo import register_model

LAYER_NORM_EPS = 1e-6     # the indexer's key norm


@dataclasses.dataclass(frozen=True)
class GLMDSAConfig:
    """Field names are the HF ``config.json`` keys. ``n_routed_experts``
    is the router's width; ``held_first`` / ``held_count`` say which of
    them this chip holds (0 held = all of them). ``vocab_size`` is the
    slice of the vocabulary held here."""
    vocab_size: int = 64
    hidden_size: int = 64
    num_hidden_layers: int = 3
    first_k_dense_replace: int = 1
    num_attention_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 12
    qk_rope_head_dim: int = 4
    v_head_dim: int = 16
    index_n_heads: int = 2
    index_head_dim: int = 8
    index_topk: int = 16
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    n_routed_experts: int = 32
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    held_first: int = 0
    held_count: int = 0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "GLMDSAConfig":
        """From a ``config.json`` dict (``rope_theta`` flat or under
        ``rope_parameters``); ``share``: ``held_first``, ``held_count``
        and ``dtype``."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.setdefault("rope_theta", (hf.get("rope_parameters") or {}).get(
            "rope_theta", cls.rope_theta))
        return cls(**{**kw, **share})

    @property
    def held(self) -> int:
        return self.held_count or self.n_routed_experts

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    def __post_init__(self):
        if self.held_first < 0 or \
                self.held_first + self.held > self.n_routed_experts:
            raise ValueError("held experts lie outside the router")
        if self.n_moe_layers < 1 or self.first_k_dense_replace < 0:
            raise ValueError("glm_dsa needs at least one expert layer")


def init_params(cfg: GLMDSAConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``; the router's bias small and not
    zero, so that it changes choices."""
    return jax.jit(_init_params, static_argnums=0)(cfg, key)


def _init_params(cfg: GLMDSAConfig, key):
    dt, d, h = cfg.dtype, cfg.hidden_size, cfg.num_attention_heads
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    counter = itertools.count()

    def dense(*shape, fan_in=None):
        k = jax.random.fold_in(key, next(counter))
        scale = (fan_in or shape[-2]) ** -0.5
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def ones(n):
        return jnp.ones((n,), dt)

    def ffn(width, *lead):
        return {"w1": dense(*lead, d, width), "w3": dense(*lead, d, width),
                "w2": dense(*lead, width, d)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {
            "attn_norm": ones(d),
            "attn": {
                "wq_a": dense(d, cfg.q_lora_rank),
                "q_norm": ones(cfg.q_lora_rank),
                "wq_b": dense(cfg.q_lora_rank, h * (nope + rp)),
                "wkv_a": dense(d, cfg.kv_lora_rank + rp),
                "kv_norm": ones(cfg.kv_lora_rank),
                "wkv_b": dense(cfg.kv_lora_rank, h * (nope + vd)),
                "wo": dense(h * vd, d)},
            "indexer": {
                "wq_b": dense(cfg.q_lora_rank,
                              cfg.index_n_heads * cfg.index_head_dim),
                "wk": dense(d, cfg.index_head_dim),
                "k_norm_w": ones(cfg.index_head_dim),
                "k_norm_b": jnp.zeros((cfg.index_head_dim,), dt),
                "w_proj": dense(d, cfg.index_n_heads)},
            "ffn_norm": ones(d)}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = ffn(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "gate": dense(d, cfg.n_routed_experts),
                "bias": 0.02 * dense(cfg.n_routed_experts, fan_in=1),
                "shared": ffn(cfg.n_shared_experts
                              * cfg.moe_intermediate_size),
                "experts": ffn(cfg.moe_intermediate_size, cfg.held)}
        layers.append(layer)
    return {"embed": dense(cfg.vocab_size, d, fan_in=d),
            "head": dense(d, cfg.vocab_size), "norm_f": ones(d),
            "layers": layers}


def indexer_qkw(x, c_q, ix, positions, cfg: GLMDSAConfig):
    """The indexer's projections: ``q_I`` [S, heads, dim], ``k_I`` [S,
    dim] (LayerNorm with bias), ``w`` float32 [S, heads], scaled."""
    s, hi, di = x.shape[0], cfg.index_n_heads, cfg.index_head_dim
    rp = cfg.qk_rope_head_dim

    def roped(t):
        return jnp.concatenate([rope_interleaved(
            t[..., :rp], positions, cfg.rope_theta), t[..., rp:]], -1)

    q_i = roped(_mm(c_q, ix["wq_b"]).reshape(s, hi, di))
    k = jnp.dot(x, ix["wk"], preferred_element_type=jnp.float32)
    mu = jnp.mean(k, -1, keepdims=True)
    var = jnp.mean(jnp.square(k - mu), -1, keepdims=True)
    k = ((k - mu) * jax.lax.rsqrt(var + LAYER_NORM_EPS)).astype(x.dtype) \
        * ix["k_norm_w"] + ix["k_norm_b"]
    w = jnp.dot(x, ix["w_proj"], preferred_element_type=jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    return q_i, roped(k), w


def index_scores(q_i, k_i, w):
    """``I[t, s] = sum_h w[t, h] ReLU(q_I[t, h] . k_I[s])``, float32."""
    dots = jnp.einsum("qhd,kd->qhk", q_i, k_i,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)


def attend(h, layer, cfg: GLMDSAConfig):
    """The attention half of a layer for one sequence ``h`` [S, d]."""
    s = h.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    with jax.named_scope("block/attn"):
        x = rmsnorm(h, layer["attn_norm"], cfg.rms_norm_eps)
        c_q, q, k, v = mla_qkv(x, layer["attn"], positions, cfg)
    with jax.named_scope("block/indexer"):
        q_i, k_i, w = indexer_qkw(x, c_q, layer["indexer"], positions, cfg)

    def selected(lo, hi):
        # up to index_topk keys lie at or before these queries: all kept
        if hi <= cfg.index_topk:
            return None
        with jax.named_scope("block/indexer"):
            scores = index_scores(q_i[lo:hi], k_i[:hi], w[lo:hi])
            with jax.named_scope("select"):
                causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None]
                return topk_mask(scores, cfg.index_topk, causal)

    return h + causal_attention_out(
        q, k, v, layer["attn"]["wo"], block_q=BLOCK_Q, key_mask=selected,
        scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
        scope="block/attn")


def moe_ffn(h, layer, cfg: GLMDSAConfig):
    """The expert half of a layer over tokens ``h`` [T, d] ->
    ``(h', load)``: shared expert plus this chip's routed experts'
    part, and the pairs each held expert served, int32 [held]."""
    moe = layer["moe"]
    with jax.named_scope("block/moe/route"):
        x = rmsnorm(h, layer["ffn_norm"], cfg.rms_norm_eps)
        choice, weight = sigmoid_route(x, moe, cfg.num_experts_per_tok,
                                       cfg.routed_scaling_factor)
        order, load = group_by_expert(choice, cfg.held_first, cfg.held)
    with jax.named_scope("block/moe/shared"):
        out = swiglu(x, moe["shared"])
    with jax.named_scope("block/moe/experts"):
        e = moe["experts"]
        out = out + grouped_swiglu(x, order, load, weight, e["w1"], e["w3"],
                                   e["w2"], tile=EXPERT_TILE)
        return h + out.astype(h.dtype), load


def forward(params, tokens, cfg: GLMDSAConfig):
    """``tokens`` int32 [B, S] -> ``(last_logits float32 [B, V],
    logprobs float32 [B, S], expert_load int32 [moe layers, held])``;
    the load is summed over the batch."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
    loads = []
    for layer in params["layers"]:
        h = jnp.stack([attend(h[j], layer, cfg) for j in range(b)])
        flat = h.reshape(b * s, -1)
        if "moe" in layer:
            flat, load = moe_ffn(flat, layer, cfg)
            loads.append(load)
        else:
            with jax.named_scope("block/mlp"):
                x = rmsnorm(flat, layer["ffn_norm"], cfg.rms_norm_eps)
                flat = flat + swiglu(x, layer["mlp"]).astype(flat.dtype)
        h = flat.reshape(b, s, -1)
    return latent.score(h, params, tokens, cfg.rms_norm_eps) \
        + (jnp.stack(loads),)


def frame_model(cfg: GLMDSAConfig, seq: int):
    """``(apply_fn, in_info, out_info)`` for ``tensor_filter
    framework=jax``: one int32 ``[seq]`` token frame a buffer in, the
    three tensors of :func:`forward` out."""
    return latent.frame_model(forward, cfg, seq,
                              f"{cfg.held}:{cfg.n_moe_layers}")


@register_model("glm_dsa")
def _build_glm_dsa(seq: str = "64", seed: str = "0", dtype: str = "bfloat16",
                   **sizes: str):
    """``zoo://glm_dsa?seq=64&hidden_size=64&held_count=8&...``: any
    field of :class:`GLMDSAConfig` by its name; the defaults are a tiny
    model whose sparse regime is live at ``seq`` 64."""
    cfg = latent.config_from_options(GLMDSAConfig, "glm_dsa", dtype, sizes)
    params = init_params(cfg, jax.random.PRNGKey(int(seed)))
    apply_fn, in_info, out_info = frame_model(cfg, int(seq))
    return apply_fn, params, in_info, out_info
