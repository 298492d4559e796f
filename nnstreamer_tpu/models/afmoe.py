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
  its own experts' part (``ops/grouped.py``); what the experts of other
  chips would add is not stood in for.
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
served).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.grouped import group_by_expert, grouped_swiglu
from . import latent
from .latent import (BLOCK_Q, EXPERT_TILE, _mm_heads, _scaled,
                     causal_attention_out, sigmoid_route, swiglu)
from .transformer import rmsnorm, rope
from .zoo import register_model

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


def init_params(cfg: AfmoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``; the router's bias small and not
    zero, so that it changes choices."""
    return jax.jit(_init_params, static_argnums=0)(cfg, key)


def _init_params(cfg: AfmoeConfig, key):
    dt, d, hd = cfg.dtype, cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
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
                "wq": dense(d, h * hd), "wk": dense(d, hkv * hd),
                "wv": dense(d, hkv * hd), "wg": dense(d, h * hd),
                "q_norm": ones(hd), "k_norm": ones(hd),
                "wo": dense(h * hd, d)},
            "post_attn_norm": ones(d),
            "ffn_norm": ones(d),
            "post_ffn_norm": ones(d)}
        if i < cfg.num_dense_layers:
            layer["mlp"] = ffn(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "gate": dense(d, cfg.num_experts),
                "bias": 0.02 * dense(cfg.num_experts, fan_in=1),
                "shared": ffn(cfg.num_shared_experts
                              * cfg.moe_intermediate_size),
                "experts": ffn(cfg.moe_intermediate_size, cfg.held)}
        layers.append(layer)
    return {"embed": dense(cfg.vocab_size, d, fan_in=d),
            "head": dense(d, cfg.vocab_size), "norm_f": ones(d),
            "layers": layers}


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


def moe(x, m, cfg: AfmoeConfig):
    """The expert layer over normed tokens ``x`` [T, d] -> ``(out
    float32 [T, d], load int32 [held])``: the shared expert plus this
    chip's routed experts' part, and the pairs each held expert
    served."""
    with jax.named_scope("block/moe/route"):
        choice, weight = sigmoid_route(x, m, cfg.num_experts_per_tok,
                                       cfg.route_scale)
        order, load = group_by_expert(choice, cfg.held_first, cfg.held)
    with jax.named_scope("block/moe/shared"):
        out = swiglu(x, m["shared"])
    with jax.named_scope("block/moe/experts"):
        e = m["experts"]
        routed = grouped_swiglu(x, order, load, weight, e["w1"], e["w3"],
                                e["w2"], tile=EXPERT_TILE,
                                router=cfg.num_experts)
        return out + routed, load


def ffn(a, layer, cfg: AfmoeConfig):
    """The second half of a layer over tokens ``a`` [T, d] -> ``(y,
    load)``; ``load`` is None for a dense layer."""
    eps = cfg.rms_norm_eps
    if "moe" in layer:
        with jax.named_scope("block/moe/route"):
            x = rmsnorm(a, layer["ffn_norm"], eps)
        out, load = moe(x, layer["moe"], cfg)
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
    b, s = tokens.shape
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
        if cfg.mup_enabled:
            h = _scaled(h, cfg.hidden_size ** 0.5)
    loads = []
    for layer, kind in zip(params["layers"], cfg.kinds):
        h = jnp.stack([attend(h[j], layer, kind, cfg) for j in range(b)])
        flat, load = ffn(h.reshape(b * s, -1), layer, cfg)
        if load is not None:
            loads.append(load)
        h = flat.reshape(b, s, -1)
    return latent.score(h, params, tokens, cfg.rms_norm_eps) \
        + (jnp.stack(loads),)


def frame_model(cfg: AfmoeConfig, seq: int):
    """``(apply_fn, in_info, out_info)`` for ``tensor_filter
    framework=jax``: one int32 ``[seq]`` token frame a buffer in, the
    three tensors of :func:`forward` out."""
    return latent.frame_model(forward, cfg, seq,
                              f"{cfg.held}:{cfg.n_moe_layers}")


@register_model("afmoe")
def _build_afmoe(seq: str = "64", seed: str = "0", dtype: str = "bfloat16",
                 **sizes: str):
    """``zoo://afmoe?seq=64&sliding_window=16&held_count=4&...``: any
    numeric field of :class:`AfmoeConfig` by its name
    (``global_attn_every_n_layers`` sets the layers' kinds); the defaults
    are a tiny model whose window is live at ``seq`` 64."""
    cfg = latent.config_from_options(AfmoeConfig, "afmoe", dtype, sizes)
    params = init_params(cfg, jax.random.PRNGKey(int(seed)))
    apply_fn, in_info, out_info = frame_model(cfg, int(seq))
    return apply_fn, params, in_info, out_info
