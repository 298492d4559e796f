"""Decoder with latent attention of two kinds in one stack: full latent
attention under a learned sparse-attention indexer on some layers,
sliding-window latent attention of other sizes on the others, a
headwise gate on both, and a sigmoid-routed expert layer that is told
which experts it holds (the ``dots3_note`` architecture:
dots3-note-prev's ``config.json`` names every size used here).

For one sequence ``x`` [S, d], layer ``l`` of kind ``layer_types[l]``:

    a = x + Attn_l(RMSNorm(x))
    y = a + F_l(RMSNorm(a))

* **Attn_l(u)**, a ``full_attention`` layer: MLA at the unprefixed
  sizes (``models/latent.py``, the expanded form): ``c_q = RMSNorm(u
  W_qa) * sqrt(d / q_lora_rank)``, ``q = c_q W_qb`` per head ``(nope |
  rope)``; ``[c_kv | k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv) * sqrt(d /
  kv_lora_rank)`` (``apply_mla_qkv_lora_rescale``; ``k_r`` unscaled);
  ``[k_nope | v] = c_kv W_kvb`` per head; RoPE on interleaved pairs at
  ``rope_theta`` on q's rope part and on ``k_r``, which all heads share;
  scores ``q.k / sqrt(nope + rope)`` over the keys ``s <= t`` the
  indexer selects (``models/glm_dsa.py``'s, ``q_I`` from ``c_q``: the
  ``index_topk`` largest ``I[t, s <= t]``, all of them while ``t <
  index_topk``), float32 softmax. A ``sliding_attention`` layer: the
  same at the ``swa_*`` sizes and ``swa_rope_theta``, over the keys
  ``t - sliding_window_size < s <= t``, no indexer. Both kinds:
  ``Attn = (sigmoid(u W_g)_h (P v)_h) W_o``, the headwise gate one
  scalar a head (``W_g`` [d, H]).
* **F_l**, ``l < first_k_dense_replace``: ``(silu(u W_1) * (u W_3))
  W_2``, ``intermediate_size`` wide. Else ``latent.sigmoid_ffn``: a
  sigmoid ``noaux_tc`` router over all ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen, weights
  renormalised over them (``norm_topk_prob``) times
  ``routed_scaling_factor``; a shared expert; the layer is given
  ``(n_routed_experts, held_first, held_count)``, routes over all and
  computes its own experts' part.
* **Stack.** ``E[tokens]``, the layers, ``RMSNorm``, an untied head;
  ``rms_norm_eps`` throughout.

Scope names: ``embed``, ``block/attn/full`` and ``block/attn/window`` (a
layer of that kind: norm, projections, rotation, kernel, gate, output
projection), ``block/indexer`` (``select`` inside), ``block/mlp``,
``block/moe/route``, ``block/moe/shared``, ``block/moe/experts``,
``lm_head``.

Zoo entry ``zoo://dots3_note?...``: int32 token frame ``[S]`` -> three
tensors, ``last_logits`` float32 ``[V]``, ``logprobs`` float32 ``[S]``
(log-softmax of token t+1 at position t; 0 at S-1) and ``expert_load``
int32 ``[expert layers, held]`` (token-expert pairs each held expert
served). Options: any numeric field of :class:`Dots3NoteConfig` by its
name, ``seq``, ``seed``; the defaults are a tiny model whose window
and whose indexer's selection are both live at ``seq`` 64.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import glm_dsa, latent
from .latent import BLOCK_Q

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """One kind of layer's attention under the names
    ``latent.mla_attention`` and ``glm_dsa.selection`` read
    (``index_*`` on the full kind only)."""
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    """Field names are the HF ``config.json`` keys; the unprefixed
    attention sizes are the full layers', the ``swa_*`` ones the sliding
    layers'. ``n_routed_experts`` is the router's width; ``held_first``
    / ``held_count`` say which of them this chip holds (0 held = all of
    them). ``vocab_size`` is the slice of the vocabulary held here."""
    vocab_size: int = 64
    hidden_size: int = 64
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    layer_types: Tuple[str, ...] = (FULL, FULL, SLIDING, SLIDING, SLIDING)
    num_attention_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 12
    qk_rope_head_dim: int = 4
    v_head_dim: int = 16
    rope_theta: float = 1e6
    index_n_heads: int = 2
    index_head_dim: int = 8
    index_topk: int = 16
    swa_num_attention_heads: int = 2
    swa_q_lora_rank: int = 32
    swa_kv_lora_rank: int = 32
    swa_qk_nope_head_dim: int = 20
    swa_qk_rope_head_dim: int = 4
    swa_v_head_dim: int = 16
    swa_rope_theta: float = 1e4
    sliding_window_size: int = 17
    attention_gate_type: str = "headwise"
    swa_attention_gate_type: str = "headwise"
    apply_mla_qkv_lora_rescale: bool = True
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    n_routed_experts: int = 32
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    held_first: int = 0
    held_count: int = 0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "Dots3NoteConfig":
        """From a ``config.json`` dict; ``share``: ``held_first``,
        ``held_count`` and ``dtype``."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        return cls(**{**kw, **share})

    @property
    def held(self) -> int:
        return self.held_count or self.n_routed_experts

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def full(self) -> LatentSizes:
        """The full layers' attention sizes, the indexer's with them."""
        return LatentSizes(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, self.rms_norm_eps, self.index_n_heads,
            self.index_head_dim, self.index_topk)

    @property
    def sliding(self) -> LatentSizes:
        """The sliding layers' attention sizes (``swa_*``)."""
        return LatentSizes(
            self.swa_num_attention_heads, self.swa_q_lora_rank,
            self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
            self.swa_qk_rope_head_dim, self.swa_v_head_dim,
            self.swa_rope_theta, self.rms_norm_eps)

    def lora_scales(self, sizes: LatentSizes) -> Tuple[float, float]:
        """What the two latents are multiplied by after their norms
        (``apply_mla_qkv_lora_rescale``): ``sqrt(d / rank)`` each."""
        return ((self.hidden_size / sizes.q_lora_rank) ** 0.5,
                (self.hidden_size / sizes.kv_lora_rank) ** 0.5)

    def __post_init__(self):
        if self.held_first < 0 or \
                self.held_first + self.held > self.n_routed_experts:
            raise ValueError("held experts lie outside the router")
        if self.n_moe_layers < 1 or self.first_k_dense_replace < 0:
            raise ValueError("dots3_note needs at least one expert layer")
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types!r}: one of "
                             f"{SLIDING!r} / {FULL!r} for each of "
                             f"{self.num_hidden_layers} layers")
        if {self.attention_gate_type, self.swa_attention_gate_type} \
                != {"headwise"} or not self.apply_mla_qkv_lora_rescale:
            raise ValueError("only the headwise attention gate and the "
                             "rescaled latents are built")


def _init_params(cfg: Dots3NoteConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``."""
    d = cfg.hidden_size
    w = latent.Seeded(key, cfg.dtype, d)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        a = cfg.sliding if kind == SLIDING else cfg.full
        h, nope, rp = a.num_attention_heads, a.qk_nope_head_dim, \
            a.qk_rope_head_dim
        layer = {
            "attn_norm": w.ones(d),
            "attn": {
                "wq_a": w.dense(d, a.q_lora_rank),
                "q_norm": w.ones(a.q_lora_rank),
                "wq_b": w.dense(a.q_lora_rank, h * (nope + rp)),
                "wkv_a": w.dense(d, a.kv_lora_rank + rp),
                "kv_norm": w.ones(a.kv_lora_rank),
                "wkv_b": w.dense(a.kv_lora_rank, h * (nope + a.v_head_dim)),
                "wg": w.dense(d, h),
                "wo": w.dense(h * a.v_head_dim, d)},
            "ffn_norm": w.ones(d)}
        if kind == FULL:
            layer["indexer"] = {
                "wq_b": w.dense(a.q_lora_rank,
                                a.index_n_heads * a.index_head_dim),
                "wk": w.dense(d, a.index_head_dim),
                "k_norm_w": w.ones(a.index_head_dim),
                "k_norm_b": jnp.zeros((a.index_head_dim,), cfg.dtype),
                "w_proj": w.dense(d, a.index_n_heads)}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = w.ffn(cfg.intermediate_size)
        else:
            layer["moe"] = w.moe(
                cfg.n_routed_experts,
                shared=cfg.n_shared_experts * cfg.moe_intermediate_size,
                width=cfg.moe_intermediate_size, held=cfg.held)
        layers.append(layer)
    return {"embed": w.dense(cfg.vocab_size, d, fan_in=d),
            "head": w.dense(d, cfg.vocab_size), "norm_f": w.ones(d),
            "layers": layers}


init_params = latent.jitted_init(_init_params)


def attend(h, layer, kind: str, cfg: Dots3NoteConfig):
    """The attention half of a layer of ``kind`` for one sequence ``h``
    [S, d]: the sliding kind under its window, the full kind under its
    indexer, each at its own sizes and with its own rescale."""
    sizes = cfg.sliding if kind == SLIDING else cfg.full
    q_scale, kv_scale = cfg.lora_scales(sizes)
    if kind == SLIDING:
        return latent.mla_attention(
            h, layer, sizes, block_q=BLOCK_Q, scope="block/attn/window",
            q_scale=q_scale, kv_scale=kv_scale,
            window=cfg.sliding_window_size)
    return latent.mla_attention(
        h, layer, sizes, block_q=BLOCK_Q, scope="block/attn/full",
        q_scale=q_scale, kv_scale=kv_scale,
        select=functools.partial(glm_dsa.selection, layer["indexer"], sizes))


def forward(params, tokens, cfg: Dots3NoteConfig):
    """``tokens`` int32 [B, S] -> ``(last_logits float32 [B, V],
    logprobs float32 [B, S], expert_load int32 [expert layers, held])``;
    the load is summed over the batch."""
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
    ffn = functools.partial(
        latent.sigmoid_ffn, top=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, held_first=cfg.held_first,
        eps=cfg.rms_norm_eps)
    return latent.decode(
        params, h, tokens, cfg.rms_norm_eps,
        [functools.partial(attend, kind=kind, cfg=cfg)
         for kind in cfg.layer_types], ffn)


frame_model = latent.frame_model(
    forward, lambda cfg: f"{cfg.held}:{cfg.n_moe_layers}")
latent.register("dots3_note", Dots3NoteConfig, init_params, frame_model)
