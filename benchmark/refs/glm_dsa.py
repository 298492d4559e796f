"""Plain reference of the ``glm_dsa`` configurations (GLM-5's
``glm_moe_dsa`` block): the scoring pass over one token sequence in
straightforward ``jax.numpy`` and float32 at ``highest`` precision,
layer by layer: no cache, no kernel, no sorting of tokens by expert, no
blocking beyond what fits it on the chip (one layer part's weights are
converted to float32 at a time, attention runs a block of queries at a
time over all keys). Imports nothing of the program; reads the
benchmark's own weights by the names the configuration file's builder
gave them (embed, head, norm_f, layers[i].{attn_norm, attn.{wq_a,
q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo}, indexer.{wq_b, wk, k_norm_w,
k_norm_b, w_proj}, ffn_norm, mlp.{w1,w3,w2} | moe.{gate, bias,
shared.{w1,w3,w2}, experts.{w1,w3,w2}}}).

The layer, from the published config's names:

* MLA: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` per head (nope |
  rope); ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; ``[k_nope
  | v] = c_kv W_kvb`` per head; RoPE (theta ``rope_theta``, interleaved
  pairs) on q's rope part and on ``k_r``, which all heads share; scores
  over ``qk_head_dim ** -0.5``, keys ``s <= t`` and ``s in sel(t)``.
* Indexer: ``q_I = c_q W_Iq`` (heads x dim), ``k_I = LayerNorm(x W_Ik)``,
  RoPE on the first ``qk_rope_head_dim`` of each; ``I[t,s] = sum_h w[t,h]
  ReLU(q_I[t,h] . k_I[s])``, ``w = x W_Iw (heads x dim) ** -0.5``;
  ``sel(t)``: the ``index_topk`` largest ``I[t, s <= t]``, ties to the
  lower index (a stable sort), all of them while ``t < index_topk``.
* Router (noaux_tc, one group): ``s = sigmoid(x W_g)``; the top
  ``num_experts_per_tok`` of ``s + bias``; weights ``s_e / (sum_chosen s +
  1e-20) * routed_scaling_factor``. Output ``x + shared(x) + sum over
  chosen experts that are held here``: this chip's share (experts
  ``held_first`` .. ``held_first + held``), what the others would add
  left out, as in the program.

Departures from the published implementation: no Hadamard rotation of
``q_I`` / ``k_I`` (orthogonal: it changes no product) and no fp8
quantisation of them (the published kernels' choice, not the model's);
the indexer's key norm is a LayerNorm with bias, eps 1e-6; the
multi-token-prediction layer is not run (it adds nothing to a scoring
pass).

``select_dtype`` is a diagnostic, never a decider: the index scores and
the router's ``s + bias`` rounded to that dtype before the discrete
choices are made, everything else float32 - how much of each compared
number the choices alone move.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .quant import make_dot, make_prep

HI = jax.lax.Precision.HIGHEST
LAYER_NORM_EPS = 1e-6
QUERY_BLOCK = 256


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """Interleaved pairs (2i, 2i+1); x [S, ..., D], position = row."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def _rope_head(x, n, theta):
    return jnp.concatenate([_rope(x[..., :n], theta), x[..., n:]], -1)


def _top_rank(values, k):
    """bool: the k largest of each row, the lower index first among
    equals (a stable descending sort, then each entry's place in it)."""
    values = jnp.where(values == 0, 0.0, values)         # -0.0 is 0.0
    order = jnp.argsort(-values, axis=-1, stable=True)
    return jnp.argsort(order, axis=-1) < k


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "index_heads", "topk", "theta", "eps",
    "precision", "select_dtype"))
def _attention(h, p, *, heads, nope, rope, index_heads, topk, theta, eps,
               precision, select_dtype):
    dot, prep = make_dot(precision), make_prep(precision)
    a, ix = p["attn"], p["indexer"]
    s = h.shape[0]
    x = _rms(h, p["attn_norm"], eps)
    c_q = _rms(dot(x, a["wq_a"]), a["q_norm"], eps)
    q = dot(c_q, a["wq_b"]).reshape(s, heads, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = dot(x, a["wkv_a"])
    r = a["kv_norm"].shape[0]
    c_kv = _rms(kv[:, :r], a["kv_norm"], eps)
    k_r = _rope(kv[:, r:], theta)
    kvb = dot(c_kv, a["wkv_b"]).reshape(s, heads, -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_r[:, None, :], (s, heads, rope))], -1)
    v = kvb[..., nope:]

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

    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :]
        dots = jnp.einsum("qhd,kd->qhk", prep(q_i[lo:hi]), prep(k_i),
                          precision=HI)
        index = jnp.einsum("qhk,qh->qk", jax.nn.relu(dots), w[lo:hi],
                           precision=HI)
        if select_dtype is not None:
            index = index.astype(select_dtype).astype(jnp.float32)
        keep = causal & _top_rank(jnp.where(causal, index, -jnp.inf), topk)
        scores = jnp.einsum("qhd,khd->hqk", prep(q[lo:hi]), prep(k),
                            precision=HI) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", prep(probs), prep(v),
                               precision=HI))
    ctx = jnp.concatenate(outs).reshape(s, -1)
    return h + dot(ctx, a["wo"])


def _swiglu(x, p, dot):
    return dot(jax.nn.silu(dot(x, p["w1"])) * dot(x, p["w3"]), p["w2"])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _dense_mlp(h, norm, p, *, eps, precision):
    return h + _swiglu(_rms(h, norm, eps), p, make_dot(precision))


@functools.partial(jax.jit, static_argnames=(
    "top", "scaling", "eps", "precision", "select_dtype"))
def _route(h, norm, gate, bias, *, top, scaling, eps, precision,
           select_dtype):
    x = _rms(h, norm, eps)
    s = jax.nn.sigmoid(make_dot(precision)(x, gate))
    biased = s + bias.astype(jnp.float32)
    if select_dtype is not None:
        biased = biased.astype(select_dtype).astype(jnp.float32)
    chosen = _top_rank(biased, top)                   # [S, router width]
    weight = jnp.where(chosen, s, 0.0)
    weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20) * scaling
    return x, chosen, weight


@functools.partial(jax.jit, static_argnames=("precision",))
def _one_expert(x, w1, w3, w2, weight, *, precision):
    return _swiglu(x, {"w1": w1, "w3": w3, "w2": w2},
                   make_dot(precision)) * weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, norm, head, tokens, *, eps, precision):
    logits = make_dot(precision)(_rms(h, norm, eps), head)
    logp = jax.nn.log_softmax(logits, -1)
    nxt = jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1)[:, 0]
    return logits[-1], jnp.concatenate([nxt, jnp.zeros((1,), jnp.float32)])


def forward(weights, tokens, sizes: dict, precision: str = "f32",
            select_dtype=None):
    """One int32 sequence [S] -> ``(last_logits float32 [V], logprobs
    float32 [S], expert_load int32 [expert layers, held])`` as numpy.
    The held experts are those of the weights; which of the router's
    they are comes from ``sizes['expert_rank']`` (0 where absent)."""
    eps = float(sizes["rms_norm_eps"])
    tokens = jnp.asarray(tokens, jnp.int32)
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    loads = []
    for layer in weights["layers"]:
        h = _attention(
            h, {k: layer[k] for k in ("attn_norm", "attn", "indexer")},
            heads=int(sizes["num_attention_heads"]),
            nope=int(sizes["qk_nope_head_dim"]),
            rope=int(sizes["qk_rope_head_dim"]),
            index_heads=int(sizes["index_n_heads"]),
            topk=int(sizes["index_topk"]), theta=float(sizes["rope_theta"]),
            eps=eps, precision=precision, select_dtype=select_dtype)
        if "mlp" in layer:
            h = _dense_mlp(h, layer["ffn_norm"], layer["mlp"], eps=eps,
                           precision=precision)
            continue
        moe = layer["moe"]
        x, chosen, weight = _route(
            h, layer["ffn_norm"], moe["gate"], moe["bias"],
            top=int(sizes["num_experts_per_tok"]),
            scaling=float(sizes["routed_scaling_factor"]), eps=eps,
            precision=precision, select_dtype=select_dtype)
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
