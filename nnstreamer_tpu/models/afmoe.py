"""Decoder whose layers differ in kind inside one stack: grouped-query
attention over a sliding window on most layers and over every earlier
key on the others, a gate on the attention's output, norms on both
sides of each sublayer, and a sigmoid-routed expert layer that is told
which experts it holds (the ``afmoe`` architecture: Trinity-Mini's
``config.json`` names every size used here).

For one sequence ``x`` [S, d], layer ``l`` of kind ``layer_types[l]``:

    a = x + RMSNorm_post_attn(Attn_l(RMSNorm_in(x)))
    y = a + RMSNorm_post_mlp(F_l(RMSNorm_pre_mlp(a)))

* **Attn_l(u).** ``q = u W_q`` [S, H, hd], ``k = u W_k``, ``v = u W_v``
  [S, H_kv, hd], ``g = u W_g`` [S, H x hd], no biases; ``q <-
  RMSNorm_hd(q)``, ``k <- RMSNorm_hd(k)`` (a learned weight over the
  head's ``hd``). On a ``sliding_attention`` layer ``q`` and ``k`` are
  rotated (RoPE over the whole head, ``rope_theta``, half-split pairs
  ``(i, i + hd / 2)``, no scaling) and query ``t`` sees keys ``t -
  sliding_window < s <= t``; on a ``full_attention`` layer no position
  is encoded at all and ``t`` sees every ``s <= t``. Query head ``h``
  reads key/value head ``h // (H / H_kv)``; scores over ``sqrt(hd)``,
  softmax in float32; ``Attn = ((P v) * sigmoid(g)) W_o``. Both kinds
  run ``ops/sparse_attention.py``'s one kernel: the window is its
  ``window``, the shared heads its ``H_kv``; K and V are never repeated.
* **F_l**, ``l < num_dense_layers``: ``(silu(u W_1) * (u W_3)) W_2``,
  ``intermediate_size`` wide. Else ``MoE(u) = Shared(u) + sum over the
  chosen e of w_e SwiGLU_e(u)``: ``r = sigmoid(u W_r)`` float32 over
  all ``num_experts``; the ``num_experts_per_tok`` largest of ``r + b``
  are chosen (``b`` the balancing bias, for the choice only; one group:
  no group limit); ``w = r[chosen] / (sum w + 1e-20)`` (``route_norm``)
  ``x route_scale`` (``latent.sigmoid_route``, GLM-5's router equation
  for equation); ``Shared`` is a SwiGLU ``moe_intermediate_size x
  num_shared_experts`` wide. The layer is given ``(num_experts,
  held_first, held_count)``, routes over the whole router and computes
  its own experts' part (``latent.routed_experts``).
* **Stack.** ``h0 = E[tokens] * sqrt(d)`` (``mup_enabled``), the layers,
  ``RMSNorm``, an untied head; ``rms_norm_eps``.

Scope names: ``embed``, ``block/attn/window`` and ``block/attn/full``
(a layer of that kind: norms, projections, rotation, kernel, gate,
output projection), ``block/mlp`` (the dense layers), ``block/moe/route``,
``block/moe/shared``, ``block/moe/experts``, ``lm_head``.

Zoo entry ``zoo://afmoe?...``: int32 token frame ``[S]`` -> three
tensors, ``last_logits`` float32 ``[V]``, ``logprobs`` float32 ``[S]``
(log-softmax of token t+1 at position t; 0 at S-1) and ``expert_load``
int32 ``[expert layers, held]`` (token-expert pairs each held expert
served). Options: any numeric field of :class:`AfmoeConfig` by its name
(``global_attn_every_n_layers`` sets the layers' kinds), ``seq``,
``seed``; the defaults are a tiny model whose window is live at ``seq``
64.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import latent
from .latent import (BLOCK_Q, _mm_heads, _scaled, causal_attention_out,
                     routed_experts, sigmoid_route, swiglu)
from .transformer import rmsnorm, rope

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Field names are the HF ``config.json`` keys. ``num_experts`` is
    the router's width; ``held_first`` / ``held_count`` say which of
    them this chip holds (0 held = all of them). ``layer_types`` names
    each layer's attention; empty, every ``global_attn_every_n_layers``-th
    layer is full and the others slide. ``vocab_size`` is the slice of
    the vocabulary held here."""
    vocab_size: int = 64
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_dense_layers: int = 1
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    num_experts: int = 16
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    sliding_window: int = 16
    global_attn_every_n_layers: int = 4
    layer_types: Tuple[str, ...] = ()
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    held_first: int = 0
    held_count: int = 0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "AfmoeConfig":
        """From a ``config.json`` dict; ``share``: ``held_first``,
        ``held_count`` and ``dtype``."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw["layer_types"] = tuple(kw.get("layer_types") or ())
        return cls(**{**kw, **share})

    @property
    def held(self) -> int:
        return self.held_count or self.num_experts

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's attention, ``num_hidden_layers`` long."""
        n = self.global_attn_every_n_layers
        return self.layer_types or tuple(
            FULL if (i + 1) % n == 0 else SLIDING
            for i in range(self.num_hidden_layers))

    def __post_init__(self):
        if self.held_first < 0 or \
                self.held_first + self.held > self.num_experts:
            raise ValueError("held experts lie outside the router")
        if self.n_moe_layers < 1 or self.num_dense_layers < 0:
            raise ValueError("afmoe needs at least one expert layer")
        if len(self.kinds) != self.num_hidden_layers \
                or set(self.kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types!r}: one of "
                             f"{SLIDING!r} / {FULL!r} for each layer")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the key/value heads do not divide the heads")
        if self.score_func != "sigmoid" or not self.route_norm:
            raise ValueError("only the sigmoid router that renormalises "
                             "the chosen weights is built")


def _init_params(cfg: AfmoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``."""
    d, hd = cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    w = latent.Seeded(key, cfg.dtype, d)
    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {
            "attn_norm": w.ones(d),
            "attn": {
                "wq": w.dense(d, h * hd), "wk": w.dense(d, hkv * hd),
                "wv": w.dense(d, hkv * hd), "wg": w.dense(d, h * hd),
                "q_norm": w.ones(hd), "k_norm": w.ones(hd),
                "wo": w.dense(h * hd, d)},
            "post_attn_norm": w.ones(d),
            "ffn_norm": w.ones(d),
            "post_ffn_norm": w.ones(d)}
        if i < cfg.num_dense_layers:
            layer["mlp"] = w.ffn(cfg.intermediate_size)
        else:
            layer["moe"] = w.moe(
                cfg.num_experts,
                shared=cfg.num_shared_experts * cfg.moe_intermediate_size,
                width=cfg.moe_intermediate_size, held=cfg.held)
        layers.append(layer)
    return {"embed": w.dense(cfg.vocab_size, d, fan_in=d),
            "head": w.dense(d, cfg.vocab_size), "norm_f": w.ones(d),
            "layers": layers}


init_params = latent.jitted_init(_init_params)


def attend(h, layer, kind: str, cfg: AfmoeConfig):
    """The attention half of a layer of ``kind`` for one sequence ``h``
    [S, d] -> ``a``."""
    a, eps = layer["attn"], cfg.rms_norm_eps
    sliding = kind == SLIDING
    scope = "block/attn/window" if sliding else "block/attn/full"
    with jax.named_scope(scope):
        x = rmsnorm(h, layer["attn_norm"], eps)
        # [heads, S, hd] as the products write them (the weights a head
        # at a time with the stream's dimension last, re-laid once per
        # load: filters/prepare.py); the kernel's and the rotation's
        # [S, heads, hd] are views
        q, k, v, gate = (jnp.transpose(t, (1, 0, 2)) for t in (
            *latent.qkv_heads(x, a, cfg.head_dim, eps),
            _mm_heads(x, latent.head_major(a["wg"], cfg.head_dim))))
        if sliding:
            positions = jnp.arange(h.shape[0], dtype=jnp.int32)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    out = causal_attention_out(
        q, k, v, a["wo"], scale=cfg.head_dim ** -0.5, block_q=BLOCK_Q,
        window=cfg.sliding_window if sliding else None, gate=gate,
        scope=scope)
    with jax.named_scope(scope):
        return h + rmsnorm(out, layer["post_attn_norm"], eps)


def ffn(a, layer, cfg: AfmoeConfig):
    """The second half of a layer over tokens ``a`` [T, d] -> ``(y,
    load)``; ``load`` is None for a dense layer."""
    eps = cfg.rms_norm_eps
    if "moe" in layer:
        m = layer["moe"]
        with jax.named_scope("block/moe/route"):
            x = rmsnorm(a, layer["ffn_norm"], eps)
            choice, weight = sigmoid_route(x, m, cfg.num_experts_per_tok,
                                           cfg.route_scale)
        out, load = routed_experts(x, m, choice, weight, cfg.held_first)
        with jax.named_scope("block/moe/experts"):
            return a + rmsnorm(out.astype(a.dtype), layer["post_ffn_norm"],
                               eps), load
    with jax.named_scope("block/mlp"):
        x = rmsnorm(a, layer["ffn_norm"], eps)
        out = swiglu(x, layer["mlp"]).astype(a.dtype)
        return a + rmsnorm(out, layer["post_ffn_norm"], eps), None


def forward(params, tokens, cfg: AfmoeConfig):
    """``tokens`` int32 [B, S] -> ``(last_logits float32 [B, V],
    logprobs float32 [B, S], expert_load int32 [expert layers, held])``;
    the load is summed over the batch."""
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
        if cfg.mup_enabled:
            h = _scaled(h, cfg.hidden_size ** 0.5)
    return latent.decode(
        params, h, tokens, cfg.rms_norm_eps,
        [functools.partial(attend, kind=kind, cfg=cfg) for kind in cfg.kinds],
        functools.partial(ffn, cfg=cfg))


frame_model = latent.frame_model(
    forward, lambda cfg: f"{cfg.held}:{cfg.n_moe_layers}")
latent.register("afmoe", AfmoeConfig, init_params, frame_model)
