"""Decoder whose layers differ in the kind of their mixer: a gated
delta-rule linear attention (KDA: a state carried from position to
position, no softmax, no keys kept) on most layers, a latent attention
without any position encoding on the others; a dense first layer and a
sigmoid-routed expert layer, told which experts it holds, after it (the
``kimi_linear`` architecture: Kimi-Linear's ``config.json`` names every
size used here).

For one sequence ``h`` [S, d], every layer pre-norm with a residual
after each sublayer:

    h = h + Mixer_l(RMSNorm(h))
    h = h + F_l(RMSNorm(h))

* **KDA** (a layer of ``linear_attn_config.kda_layers``, 1-based; ``H``
  heads of ``dk = dv = head_dim``; no bias anywhere). ``q~ =
  silu(conv(x W_q))``, ``k~ = silu(conv(x W_k))``, ``v = silu(conv(x
  W_v))``: ``conv`` a causal depthwise convolution over time,
  ``short_conv_kernel_size`` taps a channel (``y_t = sum_i w_i
  u_(t-3+i)``, zeros before the sequence). Per head ``q = q~ /
  sqrt(|q~|^2 + 1e-6) * dk^-0.5``, ``k = k~ / sqrt(|k~|^2 + 1e-6)``
  (taken inside ``ops/kda.py``'s kernels, which read ``q~``, ``k~``); a
  log-decay a channel ``a = -exp(A_log_h) * softplus((x W_f1) W_f2 +
  dt_bias)`` and ``beta = sigmoid(x W_b)``, both float32; the state
  ``S_t = (I - beta_t k_t k_t^T) diag(exp(a_t)) S_(t-1) + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t`` (``ops/kda.py``, the chunked form); ``y =
  RMSNorm_dv(o; w) * sigmoid((x W_g1) W_g2)``; ``y W_o``.
* **MLA** (a layer of ``full_attn_layers``): ``models/latent.py``'s
  projections without a query latent (``q_lora_rank`` null: ``q = x
  W_q``) and without rotation (``mla_use_nope``): ``k_h = [c W_kb,h^nope
  | k_shared]``, the shared part the same for every head and as the
  projection gave it; causal softmax over ``sqrt(nope + rope)`` through
  ``ops/sparse_attention.py``'s one kernel.
* **F_l**, ``l < first_k_dense_replace``: ``(silu(u W_1) * (u W_3))
  W_2``, ``intermediate_size`` wide. Else ``Shared(u) + sum over the
  chosen e of w_e SwiGLU_e(u)``: ``latent.sigmoid_route`` (GLM-5's and
  Trinity's router equation for equation: sigmoid scores, the bias for
  the choice only, ``moe_renormalize``, ``routed_scaling_factor``; one
  group), the shared expert unweighted. The layer is given
  ``(num_experts, held_first, held_count)``, routes over the whole
  router and computes its own experts' part (``ops/grouped.py``, which
  takes the kernel's path where the share is at least half the router);
  what the experts of other chips would add is not stood in for.
* **Stack.** ``h0 = E[tokens]`` (no scale), the layers, ``RMSNorm``, an
  untied head; ``rms_norm_eps``.

Scope names: ``embed``, ``block/attn/kda`` (a KDA mixer whole: norm,
projections, convolutions, gates, the recurrence, the gated norm, the
output projection) with ``block/attn/kda/nns_kda_chunk`` inside it (the
recurrence alone), ``block/attn/mla``, ``block/mlp``,
``block/moe/route``, ``block/moe/shared``, ``block/moe/experts``,
``lm_head``.

Zoo entry ``zoo://kimi_linear?...``: int32 token frame ``[S]`` -> three
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
from ..ops.kda import kda_chunked
from . import latent
from .latent import (BLOCK_Q, EXPERT_TILE, _mm, _mm_heads,
                     causal_attention_out, mla_qkv, sigmoid_route, swiglu)
from .transformer import rmsnorm
from .zoo import register_model

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Field names are the HF ``config.json`` keys; the nested
    ``linear_attn_config`` is flattened: ``kda_layers`` and
    ``full_attn_layers`` (1-based, as published) under their own names,
    its ``num_heads`` / ``head_dim`` / ``short_conv_kernel_size`` as
    ``kda_num_heads`` / ``kda_head_dim`` / ``kda_conv_kernel``. Both
    lists empty, every fourth layer is full and the others KDA.
    ``num_experts`` is the router's width; ``held_first`` /
    ``held_count`` say which of them this chip holds (0 held = all of
    them). ``kda_chunk`` is how the recurrence is cut, not what is
    computed. ``vocab_size`` is the slice of the vocabulary held
    here."""
    vocab_size: int = 64
    hidden_size: int = 64
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    intermediate_size: int = 128
    kda_layers: Tuple[int, ...] = ()
    full_attn_layers: Tuple[int, ...] = ()
    kda_num_heads: int = 4
    kda_head_dim: int = 16
    kda_conv_kernel: int = 4
    kda_chunk: int = 16
    num_attention_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    mla_use_nope: bool = True
    moe_intermediate_size: int = 32
    num_experts: int = 16
    num_experts_per_token: int = 4
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    held_first: int = 0
    held_count: int = 0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "KimiLinearConfig":
        """From a ``config.json`` dict; ``share``: ``held_first``,
        ``held_count`` and ``dtype``. A top-level ``kda_num_heads`` /
        ``kda_head_dim`` / ``kda_chunk`` goes before the nested group's
        value (the benchmark's rehearsal sizes are top-level numbers)."""
        names = {f.name for f in dataclasses.fields(cls)}
        nested = hf.get("linear_attn_config") or {}
        kw = {new: nested[old] for old, new in (
            ("num_heads", "kda_num_heads"), ("head_dim", "kda_head_dim"),
            ("short_conv_kernel_size", "kda_conv_kernel"),
            ("kda_layers", "kda_layers"),
            ("full_attn_layers", "full_attn_layers")) if old in nested}
        kw.update({k: v for k, v in hf.items() if k in names})
        for k in ("kda_layers", "full_attn_layers"):
            kw[k] = tuple(kw.get(k) or ())
        if hf.get("q_lora_rank") is not None:
            raise ValueError("only the latent attention without a query "
                             "latent (q_lora_rank null) is built")
        return cls(**{**kw, **share})

    @property
    def held(self) -> int:
        return self.held_count or self.num_experts

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer, ``num_hidden_layers`` long."""
        if not self.kda_layers and not self.full_attn_layers:
            return tuple(MLA if (i + 1) % 4 == 0 else KDA
                         for i in range(self.num_hidden_layers))
        return tuple(KDA if i + 1 in self.kda_layers else MLA
                     for i in range(self.num_hidden_layers))

    def __post_init__(self):
        if self.held_first < 0 or \
                self.held_first + self.held > self.num_experts:
            raise ValueError("held experts lie outside the router")
        if self.n_moe_layers < 1 or self.first_k_dense_replace < 0:
            raise ValueError("kimi_linear needs at least one expert layer")
        layers = sorted(self.kda_layers + self.full_attn_layers)
        if layers and layers != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                f"kda_layers {self.kda_layers!r} and full_attn_layers "
                f"{self.full_attn_layers!r} do not name each of the "
                f"{self.num_hidden_layers} layers once")
        if not self.mla_use_nope:
            raise ValueError("only the latent attention without rotation "
                             "(mla_use_nope) is built")
        if self.moe_router_activation_func != "sigmoid" \
                or not self.moe_renormalize:
            raise ValueError("only the sigmoid router that renormalises "
                             "the chosen weights is built")


def init_params(cfg: KimiLinearConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``; the router's bias small and not
    zero, so that it changes choices; ``A_log`` and ``dt_bias`` as the
    gated delta-rule layers are initialised (decays ``1 .. 16`` times a
    step of ``0.001 .. 0.1``)."""
    return jax.jit(_init_params, static_argnums=0)(cfg, key)


def _init_params(cfg: KimiLinearConfig, key):
    dt, d = cfg.dtype, cfg.hidden_size
    hk, dk = cfg.kda_num_heads, cfg.kda_head_dim
    h, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    counter = itertools.count()

    def fresh():
        return jax.random.fold_in(key, next(counter))

    def dense(*shape, fan_in=None):
        scale = (fan_in or shape[-2]) ** -0.5
        return (jax.random.normal(fresh(), shape, jnp.float32)
                * scale).astype(dt)

    def ones(n):
        return jnp.ones((n,), dt)

    def ffn(width, *lead):
        return {"w1": dense(*lead, d, width), "w3": dense(*lead, d, width),
                "w2": dense(*lead, width, d)}

    def kda():
        step = jnp.exp(jax.random.uniform(
            fresh(), (hk * dk,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "wq": dense(d, hk * dk), "wk": dense(d, hk * dk),
            "wv": dense(d, hk * dk),
            **{"conv_" + n: dense(cfg.kda_conv_kernel, hk * dk,
                                  fan_in=cfg.kda_conv_kernel) for n in "qkv"},
            "wf_a": dense(d, dk), "wf_b": dense(dk, hk * dk),
            "A_log": jnp.log(jax.random.uniform(
                fresh(), (hk,), jnp.float32, 1.0, 16.0)).astype(dt),
            # the inverse of softplus at the step
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "wb": dense(d, hk), "wg_a": dense(d, dk),
            "wg_b": dense(dk, hk * dk), "o_norm": ones(dk),
            "wo": dense(hk * dk, d)}

    def mla():
        return {
            "wq": dense(d, h * (nope + cfg.qk_rope_head_dim)),
            "wkv_a": dense(d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm": ones(cfg.kv_lora_rank),
            "wkv_b": dense(cfg.kv_lora_rank, h * (nope + cfg.v_head_dim)),
            "wo": dense(h * cfg.v_head_dim, d)}

    layers = []
    for i, kind in enumerate(cfg.kinds):
        layer = {"attn_norm": ones(d), "ffn_norm": ones(d),
                 "attn": kda() if kind == KDA else mla()}
        if i < cfg.first_k_dense_replace:
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


def kda_weights(a, cfg: KimiLinearConfig):
    """A KDA sublayer's projections that write a head's columns, from
    its leaves ``a`` alone, a head at a time with the contracted
    dimension last (``[H, dk, d]``: ``latent.mla_weights``' layout):
    ``(wq, wk, wv, wf_b, wg_b)``, so that their products come out
    head-major, as ``ops/kda.py`` reads a head. No input is in it: the
    jax filter runs it once per load (``filters/prepare.py``)."""
    return tuple(latent.head_major(a[n], cfg.kda_head_dim)
                 for n in ("wq", "wk", "wv", "wf_b", "wg_b"))


def short_conv(u, taps):
    """Causal depthwise convolution over time: ``u`` [H, S, dk],
    ``taps`` [T, H * dk] -> float32 [H, S, dk], ``y_t = sum_i taps_i
    u_(t-T+1+i)``, zeros before the sequence. The shifted rows are read
    in ``u``'s own dtype and widened a tap at a time, so no float32 copy
    of ``u`` goes through memory."""
    h, s, dk = u.shape
    n = taps.shape[0]
    taps = taps.astype(jnp.float32).reshape(n, h, 1, dk)
    padded = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(taps[i] * padded[:, i:i + s].astype(jnp.float32)
               for i in range(n))


def kda_mix(h, layer, cfg: KimiLinearConfig):
    """The KDA half of a layer for one sequence ``h`` [S, d] -> ``h +
    KDA(RMSNorm(h))``."""
    a, eps, dk = layer["attn"], cfg.rms_norm_eps, cfg.kda_head_dim
    f32 = jnp.float32
    with jax.named_scope("block/attn/kda"):
        x = rmsnorm(h, layer["attn_norm"], eps)
        wq, wk, wv, wf, wg = kda_weights(a, cfg)
        # q's and k's l2 norms and the query's scale are the kernels'
        q, k, v = (jax.nn.silu(short_conv(_mm_heads(x, w), a["conv_" + n])
                               ).astype(x.dtype)
                   for w, n in ((wq, "q"), (wk, "k"), (wv, "v")))
        step = jnp.einsum("sr,hdr->hsd", _mm(x, a["wf_a"]), wf,
                          preferred_element_type=f32) \
            + a["dt_bias"].astype(f32).reshape(-1, 1, dk)
        decay = -jnp.exp(a["A_log"].astype(f32))[:, None, None] \
            * jax.nn.softplus(step)
        beta = jax.nn.sigmoid(jnp.dot(x, a["wb"],
                                      preferred_element_type=f32)).T
        o = kda_chunked(q, k, v, decay, beta, chunk=cfg.kda_chunk)
        gate = jnp.einsum("sr,hdr->hsd", _mm(x, a["wg_a"]), wg,
                          preferred_element_type=f32)
        y = (rmsnorm(o, a["o_norm"], eps) * jax.nn.sigmoid(gate)
             ).astype(x.dtype)
        out = jnp.einsum("hsv,hvd->sd", y, a["wo"].reshape(-1, dk, h.shape[1]),
                         preferred_element_type=f32)
        return h + out.astype(h.dtype)


def mla_mix(h, layer, cfg: KimiLinearConfig):
    """The latent-attention half of a layer for one sequence ``h``
    [S, d] -> ``h + MLA(RMSNorm(h))``: no query latent, no rotation."""
    scope = "block/attn/mla"
    with jax.named_scope(scope):
        x = rmsnorm(h, layer["attn_norm"], cfg.rms_norm_eps)
        _, q, k, v = mla_qkv(x, layer["attn"], None, cfg, rope=False)
    return h + causal_attention_out(
        q, k, v, layer["attn"]["wo"], block_q=BLOCK_Q, scope=scope,
        scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)


def moe(x, m, cfg: KimiLinearConfig):
    """The expert layer over normed tokens ``x`` [T, d] -> ``(out
    float32 [T, d], load int32 [held])``: the shared expert plus this
    chip's routed experts' part, and the pairs each held expert
    served."""
    with jax.named_scope("block/moe/route"):
        choice, weight = sigmoid_route(x, m, cfg.num_experts_per_token,
                                       cfg.routed_scaling_factor)
        order, load = group_by_expert(choice, cfg.held_first, cfg.held)
    with jax.named_scope("block/moe/shared"):
        out = swiglu(x, m["shared"])
    with jax.named_scope("block/moe/experts"):
        e = m["experts"]
        routed = grouped_swiglu(x, order, load, weight, e["w1"], e["w3"],
                                e["w2"], tile=EXPERT_TILE,
                                router=cfg.num_experts)
        return out + routed, load


def ffn(h, layer, cfg: KimiLinearConfig):
    """The second half of a layer over tokens ``h`` [T, d] -> ``(h',
    load)``; ``load`` is None for a dense layer."""
    if "moe" in layer:
        with jax.named_scope("block/moe/route"):
            x = rmsnorm(h, layer["ffn_norm"], cfg.rms_norm_eps)
        out, load = moe(x, layer["moe"], cfg)
        with jax.named_scope("block/moe/experts"):
            return h + out.astype(h.dtype), load
    with jax.named_scope("block/mlp"):
        x = rmsnorm(h, layer["ffn_norm"], cfg.rms_norm_eps)
        return h + swiglu(x, layer["mlp"]).astype(h.dtype), None


def forward(params, tokens, cfg: KimiLinearConfig):
    """``tokens`` int32 [B, S] -> ``(last_logits float32 [B, V],
    logprobs float32 [B, S], expert_load int32 [expert layers, held])``;
    the load is summed over the batch."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
    loads = []
    for layer, kind in zip(params["layers"], cfg.kinds):
        mix = kda_mix if kind == KDA else mla_mix
        h = jnp.stack([mix(h[j], layer, cfg) for j in range(b)])
        flat, load = ffn(h.reshape(b * s, -1), layer, cfg)
        if load is not None:
            loads.append(load)
        h = flat.reshape(b, s, -1)
    return latent.score(h, params, tokens, cfg.rms_norm_eps) \
        + (jnp.stack(loads),)


def frame_model(cfg: KimiLinearConfig, seq: int):
    """``(apply_fn, in_info, out_info)`` for ``tensor_filter
    framework=jax``: one int32 ``[seq]`` token frame a buffer in, the
    three tensors of :func:`forward` out."""
    return latent.frame_model(forward, cfg, seq,
                              f"{cfg.held}:{cfg.n_moe_layers}")


@register_model("kimi_linear")
def _build_kimi_linear(seq: str = "64", seed: str = "0",
                       dtype: str = "bfloat16", **sizes: str):
    """``zoo://kimi_linear?seq=64&held_count=8&...``: any numeric field
    of :class:`KimiLinearConfig` by its name; the defaults are a tiny
    model of five layers, KDA, KDA, KDA, MLA, KDA, the first dense."""
    cfg = latent.config_from_options(KimiLinearConfig, "kimi_linear", dtype,
                                     sizes)
    params = init_params(cfg, jax.random.PRNGKey(int(seed)))
    apply_fn, in_info, out_info = frame_model(cfg, int(seq))
    return apply_fn, params, in_info, out_info
