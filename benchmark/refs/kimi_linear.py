"""Plain reference of the ``kimi_linear`` configurations (Kimi-Linear's
block: gated delta-rule linear-attention layers, KDA, three to one
position-free latent-attention layer, MLA; a dense first layer and a
sigmoid router over the experts after it): the scoring pass over one
token sequence in straightforward ``jax.numpy`` and float32 at
``highest`` precision, layer by layer: no cache, no kernel, no chunking
of the recurrence, no sorting of tokens by expert, no blocking beyond
what fits it on the chip (one layer part's weights are converted to
float32 at a time, the latent attention runs a block of queries at a
time over all keys, every held expert runs over every token and is
masked). **The KDA layer is the token-by-token recurrence**, a
``lax.scan`` over the positions that carries every head's ``[dk, dv]``
state, never the chunked form. Imports nothing of the program (the norm,
the stable top-k, the router, the SwiGLU and the head are
``refs/glm_dsa.py``'s: the same plain functions); reads the benchmark's
own weights by the names the configuration file's builder gave them
(embed, head, norm_f, layers[i].{attn_norm, ffn_norm, attn.{KDA: wq,
wk, wv, conv_q, conv_k, conv_v, wf_a, wf_b, A_log, dt_bias, wb, wg_a,
wg_b, o_norm, wo | MLA: wq, wkv_a, kv_norm, wkv_b, wo}, mlp.{w1,w3,w2} |
moe.{gate, bias, shared.{w1,w3,w2}, experts.{w1,w3,w2}}}).

For one sequence ``h`` [S, d], every layer ``h += Mixer(RMSNorm(h)); h
+= F(RMSNorm(h))``:

* **KDA** (``H`` heads of ``dk = dv``): ``q~ = silu(conv4(x W_q))``,
  ``k~``, ``v`` likewise (``conv4``: causal, depthwise, 4 taps a
  channel, ``y_t = sum_i w_i u_(t-3+i)``, zeros before the sequence);
  per head ``q = q~ / sqrt(|q~|^2 + 1e-6) * dk^-0.5``, ``k = k~ /
  sqrt(|k~|^2 + 1e-6)``; log-decay a channel ``a = -exp(A_log_h) *
  softplus((x W_f1) W_f2 + dt_bias)``; ``beta = sigmoid(x W_b)``;
  ``S_t = (I - beta_t k_t k_t^T) diag(exp(a_t)) S_(t-1) + beta_t k_t
  v_t^T``, ``S_0 = 0``; ``o_t = S_t^T q_t``; ``y = RMSNorm_dv(o; w) *
  sigmoid((x W_g1) W_g2)``; ``y W_o``. No bias anywhere.
* **MLA**, ``q_lora_rank`` null and ``mla_use_nope``: ``q = x W_q`` [S,
  H, nope + rope]; ``kv = x W_kva``, ``c = RMSNorm(kv[:, :r])``,
  ``k_shared = kv[:, r:]`` the same for every head and **not rotated**;
  ``k_h = [c W_kb,h^nope | k_shared]``, ``v_h = c W_kb,h^v``; causal
  softmax over ``sqrt(nope + rope)``; ``W_o``.
* **F**: the first ``first_k_dense_replace`` layers ``(silu(u W_1) *
  (u W_3)) W_2``; later ``Shared(u) + sum over the chosen e of w_e
  SwiGLU_e(u)``: ``s = sigmoid(u W_r)``; the ``num_experts_per_token``
  largest of ``s + b``, ties to the lower index; ``w = s[chosen] / (sum
  + 1e-20) x routed_scaling_factor``. Given this chip's share (experts
  ``held_first .. held_first + held``), the sum runs over the held
  experts; what the others would add is left out, as in the program.
* No embedding scale; a last RMSNorm and an untied head.

What ``config.json`` has no key for is from the model repository's
``modeling_kimi.py``, ``fla/layers/kda.py`` and ``fla/ops/kda``, and
Kimi Linear's report (arXiv 2510.26692); the configuration file repeats
each under ``assumed``: the three convolutions and their ``silu``, the
l2 norms and the query's scale, the two low-rank gates without bias, the
gated output norm's sigmoid, decay before delta, ``A_log`` / ``dt_bias``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .glm_dsa import HI, _dense_mlp, _head, _one_expert, _rms, _route
from .quant import make_dot, make_prep

QUERY_BLOCK = 256
L2_EPS = 1e-6
KDA = ("conv", "decay", "beta", "gate")     # the tests' switches of _kda


def _conv4(u, taps):
    """``u`` [S, C], ``taps`` [T, C] -> ``y_t = sum_i taps_i u_(t-T+1+i)``,
    zeros before the sequence."""
    n = taps.shape[0]
    padded = jnp.pad(u, ((n - 1, 0), (0, 0)))
    return sum(taps[i].astype(jnp.float32) * padded[i:i + u.shape[0]]
               for i in range(n))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def recurrence(q, k, v, a, beta):
    """The gated delta rule a token at a time: ``q``, ``k`` [S, H, dk],
    ``v`` [S, H, dv], ``a`` [S, H, dk] log-decays, ``beta`` [S, H], all
    float32 -> ``o`` [S, H, dv]."""
    def step(state, x):
        q, k, v, a, beta = x
        state = state * jnp.exp(a)[:, :, None]              # decay first
        seen = jnp.einsum("hd,hdv->hv", k, state, precision=HI)
        state = state + jnp.einsum("hd,hv->hdv", k,
                                   beta[:, None] * (v - seen), precision=HI)
        return state, jnp.einsum("hd,hdv->hv", q, state, precision=HI)

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, k, v, a, beta))[1]


@functools.partial(jax.jit, static_argnames=("heads", "eps", "precision")
                   + KDA)
def _kda(h, p, *, heads, eps, precision, conv=True, decay=True, beta=True,
         gate=True):
    """``h + KDA(RMSNorm(h))``. ``conv``, ``decay``, ``beta`` and
    ``gate`` are the tests' (each removed must change the result); a
    configuration never turns them off."""
    dot = make_dot(precision)
    m = p["attn"]
    s = h.shape[0]
    x = _rms(h, p["attn_norm"], eps)

    def mixed(w, taps):
        u = dot(x, w)
        return jax.nn.silu(_conv4(u, taps) if conv else u).reshape(
            s, heads, -1)

    q, k, v = (mixed(m["w" + n], m["conv_" + n]) for n in "qkv")
    q, k = _l2(q) * q.shape[-1] ** -0.5, _l2(k)
    a = -jnp.exp(m["A_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(dot(dot(x, m["wf_a"]), m["wf_b"])
                          + m["dt_bias"].astype(jnp.float32)
                          ).reshape(s, heads, -1)
    b = jax.nn.sigmoid(dot(x, m["wb"]))
    o = recurrence(q, k, v, a if decay else jnp.zeros_like(a),
                   b if beta else jnp.ones_like(b))
    y = _rms(o, m["o_norm"], eps).reshape(s, -1)
    if gate:
        y = y * jax.nn.sigmoid(dot(dot(x, m["wg_a"]), m["wg_b"]))
    return h + dot(y, m["wo"])


@functools.partial(jax.jit, static_argnames=("heads", "nope", "eps",
                                             "precision", "shared_key"))
def _mla(h, p, *, heads, nope, eps, precision, shared_key=True):
    """``h + MLA(RMSNorm(h))``: no query latent, no rotation.
    ``shared_key`` is the tests'."""
    dot, prep = make_dot(precision), make_prep(precision)
    m = p["attn"]
    s = h.shape[0]
    x = _rms(h, p["attn_norm"], eps)
    q = dot(x, m["wq"]).reshape(s, heads, -1)
    kv = dot(x, m["wkv_a"])
    r = m["kv_norm"].shape[0]
    kvb = dot(_rms(kv[:, :r], m["kv_norm"], eps), m["wkv_b"]).reshape(
        s, heads, -1)
    shared = kv[:, r:] if shared_key else jnp.zeros_like(kv[:, r:])
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        shared[:, None, :], (s, heads, shared.shape[-1]))], -1)
    v = kvb[..., nope:]
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        keep = jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.einsum("qhd,khd->hqk", prep(q[lo:hi]), prep(k),
                            precision=HI) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", prep(probs), prep(v),
                               precision=HI))
    return h + dot(jnp.concatenate(outs).reshape(s, -1), m["wo"])


def forward(weights, tokens, sizes: dict, precision: str = "f32", **without):
    """One int32 sequence [S] -> ``(last_logits float32 [V], logprobs
    float32 [S], expert_load int32 [expert layers, held])`` as numpy.
    A layer is a KDA layer where its weights hold ``A_log``. The held
    experts are those of the weights; which of the router's they are
    comes from ``sizes['expert_rank']`` (0 where absent). ``without``:
    the tests' switches of :func:`_kda` and :func:`_mla`, and
    ``router_bias`` (False: the choice is by the scores alone)."""
    eps = float(sizes["rms_norm_eps"])
    kda_off = {k: v for k, v in without.items() if k in KDA}
    tokens = jnp.asarray(tokens, jnp.int32)
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    loads = []
    for layer in weights["layers"]:
        part = {k: layer[k] for k in ("attn_norm", "attn")}
        if "A_log" in layer["attn"]:
            h = _kda(h, part, heads=int(sizes["kda_num_heads"]), eps=eps,
                     precision=precision, **kda_off)
        else:
            h = _mla(h, part, heads=int(sizes["num_attention_heads"]),
                     nope=int(sizes["qk_nope_head_dim"]), eps=eps,
                     precision=precision,
                     shared_key=without.get("shared_key", True))
        if "mlp" in layer:
            h = _dense_mlp(h, layer["ffn_norm"], layer["mlp"], eps=eps,
                           precision=precision)
            continue
        moe = layer["moe"]
        bias = moe["bias"] if without.get("router_bias", True) \
            else jnp.zeros_like(moe["bias"])
        x, chosen, weight = _route(
            h, layer["ffn_norm"], moe["gate"], bias,
            top=int(sizes["num_experts_per_token"]),
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
