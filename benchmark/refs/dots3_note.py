"""Plain reference of the ``dots3_note`` configurations (dots3-note-prev's
language model: full latent attention under a sparse-attention indexer
and sliding-window latent attention of other sizes in one stack, a
headwise gate on both, a sigmoid router over a share of the experts):
the scoring pass over one token sequence in straightforward
``jax.numpy`` and float32 at ``highest`` precision, layer by layer: no
cache, no kernel, no sorting of tokens by expert, no blocking beyond
what fits it on the chip (one layer part's weights are converted to
float32 at a time, attention runs a block of queries at a time over all
keys, the window as a mask; every held expert runs over every token and
is weighted by its routing). Imports nothing of the program (the norms,
the rotation, the stable top-k, the router, the SwiGLU and the head are
``refs/glm_dsa.py``'s: the same plain functions); reads the benchmark's
own weights by the names the configuration file's builder gave them
(embed, head, norm_f, layers[i].{attn_norm, attn.{wq_a, q_norm, wq_b,
wkv_a, kv_norm, wkv_b, wg, wo}, indexer.{wq_b, wk, k_norm_w, k_norm_b,
w_proj} (full layers), ffn_norm, mlp.{w1,w3,w2} | moe.{gate, bias,
shared.{w1,w3,w2}, experts.{w1,w3,w2}}}).

For one sequence ``x`` [S, d], layer ``l`` of kind ``layer_types[l]``:
``a = x + Attn_l(RMSNorm(x))``, ``y = a + F_l(RMSNorm(a))``.

* ``Attn_l(u)``, ``full_attention``: ``c_q = RMSNorm(u W_qa) * sqrt(d /
  q_lora_rank)``, ``q = c_q W_qb`` per head (nope | rope); ``[c_kv |
  k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv) * sqrt(d / kv_lora_rank)``;
  ``[k_nope | v] = c_kv W_kvb`` per head; RoPE (interleaved pairs,
  ``rope_theta``) on q's rope part and on ``k_r``, which all heads
  share; scores over ``sqrt(nope + rope)``, keys ``s <= t`` with ``s in
  sel(t)``: the indexer's (``q_I = c_q W_Iq``, ``k_I = LayerNorm(u
  W_Ik)``, RoPE on the first ``qk_rope_head_dim`` of each, ``I[t,s] =
  sum_h w[t,h] ReLU(q_I[t,h].k_I[s])``, ``w = u W_Iw (heads x dim) **
  -0.5``; the ``index_topk`` largest ``I[t, s <= t]``, ties to the
  lower index, all of them while ``t < index_topk``). ``sliding_attention``:
  the same with the ``swa_*`` sizes and ``swa_rope_theta``, keys ``t -
  sliding_window_size < s <= t``, no indexer. Both: ``o_h =
  sigmoid(u W_g)[h] (P v)_h``, ``Attn = o W_o``.
* ``F_l``, ``l < first_k_dense_replace``: ``(silu(u W_1) * (u W_3))
  W_2``. Else ``shared(u) + sum over the chosen experts held here of
  w_e SwiGLU_e(u)``: ``s = sigmoid(u W_g)``; the
  ``num_experts_per_tok`` largest of ``s + bias``, ties to the lower
  index; ``w = s[chosen] / (sum + 1e-20) x routed_scaling_factor``.
  Given this chip's share (experts ``held_first .. held_first + held``),
  what the others would add is left out, as in the program.
* ``E[tokens]``, a last RMSNorm and an untied head.

What ``config.json`` does not spell out, and the configuration file
repeats under ``assumed``: the headwise gate's input (the normed
attention input) and its form (a sigmoid, one scalar a head, on the
heads' outputs before ``W_o``); the rescale factors ``sqrt(d / rank)``
(``apply_mla_qkv_lora_rescale``, LongCat's ``mla_scale_*_lora``); that
the window of ``sliding_window_size`` counts the query's own key; the
indexer's key norm (a LayerNorm with bias, eps 1e-6), its ``q_I`` from
the rescaled ``c_q``, no Hadamard rotation and no fp8 in it (as
``refs/glm_dsa.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .glm_dsa import (HI, LAYER_NORM_EPS, _dense_mlp, _head, _one_expert,
                      _rms, _rope, _rope_head, _route, _top_rank)
from .quant import make_dot, make_prep

SLIDING = "sliding_attention"
QUERY_BLOCK = 256


def _sizes(sizes: dict, sliding: bool) -> dict:
    """A layer kind's attention sizes by the unprefixed names."""
    pre = "swa_" if sliding else ""
    out = {k: int(sizes[pre + k]) for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim")}
    out["rope_theta"] = float(sizes[pre + "rope_theta"])
    return out


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "theta", "q_scale", "kv_scale", "window",
    "index_heads", "topk", "eps", "precision"))
def _attention(h, p, *, heads, nope, rope, theta, q_scale, kv_scale, window,
               index_heads, topk, eps, precision):
    dot, prep = make_dot(precision), make_prep(precision)
    a = p["attn"]
    s = h.shape[0]
    x = _rms(h, p["attn_norm"], eps)
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
    gate = jax.nn.sigmoid(dot(x, a["wg"]))                  # [S, H]

    if topk:
        ix = p["indexer"]
        q_i = _rope_head(dot(c_q, ix["wq_b"]).reshape(s, index_heads, -1),
                         rope, theta)
        k_i = dot(x, ix["wk"])
        mu = jnp.mean(k_i, -1, keepdims=True)
        var = jnp.mean(jnp.square(k_i - mu), -1, keepdims=True)
        k_i = (k_i - mu) * jax.lax.rsqrt(var + LAYER_NORM_EPS) \
            * ix["k_norm_w"].astype(jnp.float32) \
            + ix["k_norm_b"].astype(jnp.float32)
        k_i = _rope_head(k_i, rope, theta)
        w = dot(x, ix["w_proj"]) * (index_heads * q_i.shape[-1]) ** -0.5

    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} tokens are no whole number of blocks of "
                         f"{block} queries")

    def attend(lo):
        rows = jax.lax.dynamic_slice_in_dim
        ahead = lo + jnp.arange(block)[:, None] - jnp.arange(s)[None, :]
        keep = ahead >= 0
        if window:
            keep &= ahead < window
        if topk:
            dots = jnp.einsum("qhd,kd->qhk", prep(rows(q_i, lo, block)),
                              prep(k_i), precision=HI)
            index = jnp.einsum("qhk,qh->qk", jax.nn.relu(dots),
                               rows(w, lo, block), precision=HI)
            keep &= _top_rank(jnp.where(keep, index, -jnp.inf), topk)
        scores = jnp.einsum("qhd,khd->hqk", prep(rows(q, lo, block)),
                            prep(k), precision=HI) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", prep(probs), prep(v),
                          precision=HI) * rows(gate, lo, block)[:, :, None]

    # one block of queries at a time over every key, in turn
    outs = jax.lax.map(attend, jnp.arange(0, s, block))
    ctx = outs.reshape(s, -1)
    return h + dot(ctx, a["wo"])


def forward(weights, tokens, sizes: dict, precision: str = "f32"):
    """One int32 sequence [S] -> ``(last_logits float32 [V], logprobs
    float32 [S], expert_load int32 [expert layers, held])`` as numpy.
    ``sizes`` holds the configuration's numbers and its ``layer_types``.
    The held experts are those of the weights; which of the router's
    they are comes from ``sizes['expert_rank']`` (0 where absent)."""
    eps = float(sizes["rms_norm_eps"])
    d = int(sizes["hidden_size"])
    tokens = jnp.asarray(tokens, jnp.int32)
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    loads = []
    for layer, kind in zip(weights["layers"], sizes["layer_types"]):
        sliding = kind == SLIDING
        a = _sizes(sizes, sliding)
        h = _attention(
            h, {k: layer[k] for k in ("attn_norm", "attn", "indexer")
                if k in layer},
            heads=a["num_attention_heads"], nope=a["qk_nope_head_dim"],
            rope=a["qk_rope_head_dim"], theta=a["rope_theta"],
            q_scale=(d / a["q_lora_rank"]) ** 0.5,
            kv_scale=(d / a["kv_lora_rank"]) ** 0.5,
            window=int(sizes["sliding_window_size"]) if sliding else 0,
            index_heads=0 if sliding else int(sizes["index_n_heads"]),
            topk=0 if sliding else int(sizes["index_topk"]), eps=eps,
            precision=precision)
        if "mlp" in layer:
            h = _dense_mlp(h, layer["ffn_norm"], layer["mlp"], eps=eps,
                           precision=precision)
            continue
        moe = layer["moe"]
        x, chosen, weight = _route(
            h, layer["ffn_norm"], moe["gate"], moe["bias"],
            top=int(sizes["num_experts_per_tok"]),
            scaling=float(sizes["routed_scaling_factor"]), eps=eps,
            precision=precision, select_dtype=None)
        held = moe["experts"]["w1"].shape[0]
        first = held * int(sizes.get("expert_rank", 0))
        h = _dense_mlp(h, layer["ffn_norm"], moe["shared"], eps=eps,
                       precision=precision)
        for e in range(held):
            h = h + _one_expert(x, *(moe["experts"][n][e]
                                     for n in ("w1", "w3", "w2")),
                                weight[:, first + e], precision=precision)
        loads.append(np.asarray(chosen[:, first:first + held].sum(0)))
    last, logprobs = _head(h, weights["norm_f"], weights["head"], tokens,
                           eps=eps, precision=precision)
    return (np.asarray(last), np.asarray(logprobs),
            np.stack(loads).astype(np.int32))
