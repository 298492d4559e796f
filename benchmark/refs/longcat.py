"""Plain reference of the ``longcat`` configurations (LongCat-Flash's
shortcut-connected double block): the scoring pass over one token
sequence in straightforward ``jax.numpy`` and float32 at ``highest``
precision, layer by layer: no cache, no kernel, no sorting of tokens by
expert, no blocking beyond what fits it on the chip (one layer part's
weights are converted to float32 at a time, attention runs a block of
queries at a time over all keys, every held expert runs over every token
and is masked). Imports nothing of the program (the norm, the rotation,
the stable top-k, the SwiGLU and the head are ``refs/glm_dsa.py``'s:
the same plain functions); reads the benchmark's
own weights by the names the configuration file's builder gave them
(embed, head, norm_f, layers[i].{sub[0|1].{attn_norm, attn.{wq_a,
q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo}, ffn_norm, mlp.{w1,w3,w2}},
moe.{gate, bias, experts.{w1,w3,w2}}}).

One of the published model's ``num_layers`` layers, for a sequence
``h0`` [S, d], in the order the published implementation computes it:

    a1 = h0 + MLA_0(RMSNorm_in0(h0))
    x1 = RMSNorm_post0(a1)
    m  = MoE(x1)              # the shortcut: nothing reads it until h2
    h1 = a1 + FFN_0(x1)
    a2 = h1 + MLA_1(RMSNorm_in1(h1))
    x2 = RMSNorm_post1(a2)
    h2 = a2 + FFN_1(x2) + m

* ``MLA_i(x)``: ``c_q = RMSNorm(x W_qa) * sqrt(d / q_lora_rank)``; ``q =
  c_q W_qb`` per head (nope | rope), RoPE on the rope part; ``[c_kv |
  k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv) * sqrt(d / kv_lora_rank)``;
  RoPE on ``k_r``, one for all heads; ``[k_nope | v] = c_kv W_kvb`` per
  head; scores ``q . [k_nope | k_r] * (nope + rope) ** -0.5`` over keys
  ``s <= t``, float32 softmax, ``o = (P v) W_o``. No selection.
* ``FFN_i(x) = (silu(x W_1) * (x W_3)) W_2``.
* ``MoE(x)``: ``p = softmax(x W_c)`` over all ``n_routed_experts +
  zero_expert_num`` outputs; the ``moe_topk`` largest of ``p + b`` are
  chosen, ties to the lower index; ``g_e = routed_scaling_factor * p_e``
  for the chosen; ``MoE(x) = sum_{chosen e real} g_e SwiGLU_e(x) +
  (sum_{chosen e identity} g_e) x``. Given this chip's share (real
  experts ``held_first .. held_first + held``), the sum runs over the
  held experts and the identity experts, which every chip computes
  alike; what the others would add is left out, as in the program.

Departures from the published implementation, and what ``config.json``
leaves open (the configuration file repeats them under ``assumed``):
``hidden_act`` is silu; RoPE rotates interleaved pairs ``(2i, 2i+1)``
with no scaling of the frequencies; the router's classifier has no bias
and its product runs in float32; ``e_score_correction_bias`` is added
for the choice only; the chosen weights are not renormalised; the two
MLA scales multiply the latents after their norms (the published code
multiplies q and the key's nope part after the second projection, which
is the same number; the roped key part is unscaled either way); the
embedding and the head are untied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .glm_dsa import HI, _head, _one_expert, _rms, _rope, _swiglu, _top_rank
from .quant import make_dot, make_prep

QUERY_BLOCK = 256


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "q_scale", "kv_scale", "theta", "eps",
    "precision"))
def _attention(h, norm, a, *, heads, nope, rope, q_scale, kv_scale, theta,
               eps, precision):
    """``h + MLA(RMSNorm(h))``."""
    dot, prep = make_dot(precision), make_prep(precision)
    s = h.shape[0]
    x = _rms(h, norm, eps)
    c_q = _rms(dot(x, a["wq_a"]), a["q_norm"], eps) * q_scale
    q = dot(c_q, a["wq_b"]).reshape(s, heads, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = dot(x, a["wkv_a"])
    r = a["kv_norm"].shape[0]
    c_kv = _rms(kv[:, :r], a["kv_norm"], eps) * kv_scale
    k_r = _rope(kv[:, r:], theta)
    kvb = dot(c_kv, a["wkv_b"]).reshape(s, heads, -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_r[:, None, :], (s, heads, rope))], -1)
    v = kvb[..., nope:]
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.einsum("qhd,khd->hqk", prep(q[lo:hi]), prep(k),
                            precision=HI) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", prep(probs), prep(v),
                               precision=HI))
    ctx = jnp.concatenate(outs).reshape(s, -1)
    return h + dot(ctx, a["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(h, norm, *, eps):
    return _rms(h, norm, eps)


@functools.partial(jax.jit, static_argnames=("precision",))
def _ffn(x, p, *, precision):
    return _swiglu(x, p, make_dot(precision))


@functools.partial(jax.jit, static_argnames=("top", "scaling", "precision"))
def _route(x, gate, bias, *, top, scaling, precision):
    """-> ``(chosen bool, weight float32)``, both [S, router width]."""
    p = jax.nn.softmax(make_dot(precision)(x, gate), -1)
    chosen = _top_rank(p + bias.astype(jnp.float32), top)
    return chosen, jnp.where(chosen, p, 0.0) * scaling


def moe(x, m, sizes: dict, precision: str = "f32"):
    """``MoE(x)`` of this share for normed tokens ``x`` [S, d] -> ``(out
    float32 [S, d], load int32 [held + 1])``: the held experts' part and
    the identity experts' part; the load's last entry is the identity
    experts' pairs."""
    real = int(sizes["n_routed_experts_total"])
    chosen, weight = _route(x, m["gate"], m["bias"],
                            top=int(sizes["moe_topk"]),
                            scaling=float(sizes["routed_scaling_factor"]),
                            precision=precision)
    held = m["experts"]["w1"].shape[0]
    first = held * int(sizes.get("expert_rank", 0))
    out = x * jnp.sum(weight[:, real:], -1, keepdims=True)
    for e in range(held):
        out = out + _one_expert(x, *(m["experts"][n][e]
                                     for n in ("w1", "w3", "w2")),
                                weight[:, first + e], precision=precision)
    load = jnp.concatenate([chosen[:, first:first + held].sum(0),
                            chosen[:, real:].sum()[None]])
    return out, np.asarray(load).astype(np.int32)


def forward(weights, tokens, sizes: dict, precision: str = "f32"):
    """One int32 sequence [S] -> ``(last_logits float32 [V], logprobs
    float32 [S], expert_load int32 [layers, held + 1])`` as numpy. The
    held experts are those of the weights; which of the router's they
    are comes from ``sizes['expert_rank']`` (0 where absent). ``sizes``
    holds the configuration's numbers and its two ``mla_scale_*``
    flags."""
    eps = float(sizes["rms_norm_eps"])
    d = int(sizes["hidden_size"])
    if sizes["zero_expert_type"] != "identity":
        raise ValueError("the reference knows identity zero experts only")
    how = dict(
        heads=int(sizes["num_attention_heads"]),
        nope=int(sizes["qk_nope_head_dim"]),
        rope=int(sizes["qk_rope_head_dim"]),
        q_scale=(d / int(sizes["q_lora_rank"])) ** 0.5
        if sizes["mla_scale_q_lora"] else 1.0,
        kv_scale=(d / int(sizes["kv_lora_rank"])) ** 0.5
        if sizes["mla_scale_kv_lora"] else 1.0,
        theta=float(sizes["rope_theta"]), eps=eps, precision=precision)
    tokens = jnp.asarray(tokens, jnp.int32)
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    loads = []
    for layer in weights["layers"]:
        first, second = layer["sub"]
        a1 = _attention(h, first["attn_norm"], first["attn"], **how)
        x1 = _norm(a1, first["ffn_norm"], eps=eps)
        shortcut, load = moe(x1, layer["moe"], sizes, precision)
        loads.append(load)
        h1 = a1 + _ffn(x1, first["mlp"], precision=precision)
        a2 = _attention(h1, second["attn_norm"], second["attn"], **how)
        x2 = _norm(a2, second["ffn_norm"], eps=eps)
        h = a2 + _ffn(x2, second["mlp"], precision=precision) + shortcut
    last, logprobs = _head(h, weights["norm_f"], weights["head"], tokens,
                           eps=eps, precision=precision)
    return np.asarray(last), np.asarray(logprobs), np.stack(loads)
