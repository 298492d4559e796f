"""Plain reference of the ``afmoe`` configurations (Trinity-Mini's block:
window and full grouped-query layers in one stack, a gate on the
attention's output, norms on both sides of each sublayer, a sigmoid
router over all of a layer's experts): the scoring pass over one token
sequence in straightforward ``jax.numpy`` and float32 at ``highest``
precision, layer by layer: no cache, no kernel, no sorting of tokens by
expert, no blocking beyond what fits it on the chip (one layer part's
weights are converted to float32 at a time, attention runs a block of
queries at a time over all keys with each key/value head repeated for
its query heads, every held expert runs over every token and is
masked). Imports nothing of the program (the norm, the stable top-k,
the router, the SwiGLU and the head are ``refs/glm_dsa.py``'s: the same
plain functions); reads the benchmark's own weights by the names the
configuration file's builder gave them (embed, head, norm_f,
layers[i].{attn_norm, attn.{wq, wk, wv, wg, q_norm, k_norm, wo},
post_attn_norm, ffn_norm, post_ffn_norm, mlp.{w1,w3,w2} | moe.{gate,
bias, shared.{w1,w3,w2}, experts.{w1,w3,w2}}}).

For one sequence ``x`` [S, d], layer ``l``:

    a = x + RMSNorm_post_attn(Attn_l(RMSNorm_in(x)))
    y = a + RMSNorm_post_mlp(F_l(RMSNorm_pre_mlp(a)))

* ``Attn_l(u)``: ``q = u W_q`` [S, H, hd], ``k = u W_k``, ``v = u W_v``
  [S, H_kv, hd], ``g = u W_g`` [S, H x hd], no biases; ``q <-
  RMSNorm_hd(q)``, ``k <- RMSNorm_hd(k)``; on a ``sliding_attention``
  layer ``q``, ``k`` are rotated (RoPE over the whole head, half-split
  pairs ``(i, i + hd / 2)``, ``rope_theta``, no scaling) and query ``t``
  sees keys ``t - sliding_window < s <= t``; on a ``full_attention``
  layer no position is encoded and ``t`` sees every ``s <= t``; query
  head ``h`` reads key/value head ``h // (H / H_kv)``; scores over
  ``sqrt(hd)``, softmax; ``Attn = ((P v) * sigmoid(g)) W_o``.
* ``F_l``, ``l < num_dense_layers``: ``(silu(u W_1) * (u W_3)) W_2``.
  Else ``Shared(u) + sum over the chosen e of w_e SwiGLU_e(u)``: ``r =
  sigmoid(u W_r)``; the ``num_experts_per_tok`` largest of ``r + b``,
  ties to the lower index; ``w = r[chosen] / (sum + 1e-20) x
  route_scale``. Given this chip's share (experts ``held_first ..
  held_first + held``), the sum runs over the held experts; what the
  others would add is left out, as in the program.
* ``h0 = E[tokens] * sqrt(d)`` where ``mup_enabled``; a last RMSNorm
  and an untied head.

What ``config.json`` has no key for, from the family's public
implementation (``transformers`` ``models/afmoe/modeling_afmoe.py``) and
Arcee's Trinity report; the configuration file repeats each under
``assumed``: the gate on the attention's output (a fifth projection,
sigmoid, before ``W_o``), the q/k norms over the head, no position on
full layers, the half-split rotation, the sandwich norms, the
embedding's ``sqrt(d)`` (``mup_enabled`` is a key; what it multiplies is
the implementation's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .glm_dsa import HI, _head, _one_expert, _rms, _route, _swiglu
from .longcat import _ffn
from .quant import make_dot, make_prep

QUERY_BLOCK = 256
SLIDING = "sliding_attention"


def _rope_half(x, theta):
    """Pairs ``(i, i + D / 2)``; ``x`` [S, H, D], position = row."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "theta", "eps", "precision", "gated",
    "qk_norm", "post_norm"))
def _attention(h, p, *, heads, kv_heads, window, theta, eps, precision,
               gated=True, qk_norm=True, post_norm=True):
    """``h + RMSNorm_post(Attn(RMSNorm_in(h)))``; ``window`` None is a
    full layer (no rotation, every earlier key). ``gated``, ``qk_norm``
    and ``post_norm`` are the tests' (each removed must change the
    result); a configuration never turns them off."""
    dot, prep = make_dot(precision), make_prep(precision)
    a = p["attn"]
    s = h.shape[0]
    x = _rms(h, p["attn_norm"], eps)
    q = dot(x, a["wq"]).reshape(s, heads, -1)
    k = dot(x, a["wk"]).reshape(s, kv_heads, -1)
    v = dot(x, a["wv"]).reshape(s, kv_heads, -1)
    if qk_norm:
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    if window is not None:
        q, k = _rope_half(q, theta), _rope_half(k, theta)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        ahead = jnp.arange(lo, hi)[:, None] - jnp.arange(s)[None, :]
        keep = ahead >= 0
        if window is not None:
            keep &= ahead < window
        scores = jnp.einsum("qhd,khd->hqk", prep(q[lo:hi]), prep(k),
                            precision=HI) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", prep(probs), prep(v),
                               precision=HI))
    ctx = jnp.concatenate(outs).reshape(s, -1)
    if gated:
        ctx = ctx * jax.nn.sigmoid(dot(x, a["wg"]))
    out = dot(ctx, a["wo"])
    return h + (_rms(out, p["post_attn_norm"], eps) if post_norm else out)


@functools.partial(jax.jit, static_argnames=("eps", "post_norm"))
def _add_normed(a, out, post, *, eps, post_norm=True):
    return a + (_rms(out, post, eps) if post_norm else out)


@functools.partial(jax.jit, static_argnames=("eps", "precision", "post_norm"))
def _dense(a, pre, post, p, *, eps, precision, post_norm=True):
    out = _swiglu(_rms(a, pre, eps), p, make_dot(precision))
    return _add_normed(a, out, post, eps=eps, post_norm=post_norm)


def forward(weights, tokens, sizes: dict, precision: str = "f32", **without):
    """One int32 sequence [S] -> ``(last_logits float32 [V], logprobs
    float32 [S], expert_load int32 [expert layers, held])`` as numpy.
    The held experts are those of the weights; which of the router's
    they are comes from ``sizes['expert_rank']`` (0 where absent).
    ``sizes`` holds the configuration's numbers, its ``layer_types`` and
    ``mup_enabled``. ``without``: the tests' switches of
    :func:`_attention` (``post_norm`` takes the norm after the second
    sublayer too)."""
    eps = float(sizes["rms_norm_eps"])
    how = dict(heads=int(sizes["num_attention_heads"]),
               kv_heads=int(sizes["num_key_value_heads"]),
               theta=float(sizes["rope_theta"]), eps=eps,
               precision=precision, **without)
    post = {"post_norm": without.get("post_norm", True)}
    kinds = list(sizes["layer_types"])
    if len(kinds) != len(weights["layers"]):
        raise ValueError("layer_types does not name every layer")
    tokens = jnp.asarray(tokens, jnp.int32)
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    if sizes["mup_enabled"]:
        h = h * float(sizes["hidden_size"]) ** 0.5
    loads = []
    for layer, kind in zip(weights["layers"], kinds):
        a = _attention(
            h, {k: layer[k] for k in ("attn_norm", "attn", "post_attn_norm")},
            window=int(sizes["sliding_window"]) if kind == SLIDING else None,
            **how)
        if "mlp" in layer:
            h = _dense(a, layer["ffn_norm"], layer["post_ffn_norm"],
                       layer["mlp"], eps=eps, precision=precision, **post)
            continue
        moe = layer["moe"]
        x, chosen, weight = _route(
            a, layer["ffn_norm"], moe["gate"], moe["bias"],
            top=int(sizes["num_experts_per_tok"]),
            scaling=float(sizes["route_scale"]), eps=eps,
            precision=precision, select_dtype=None)
        held = moe["experts"]["w1"].shape[0]
        first = held * int(sizes.get("expert_rank", 0))
        out = _ffn(x, moe["shared"], precision=precision)
        for e in range(held):
            out = out + _one_expert(x, *(moe["experts"][n][e]
                                         for n in ("w1", "w3", "w2")),
                                    weight[:, first + e], precision=precision)
        h = _add_normed(a, out, layer["post_ffn_norm"], eps=eps, **post)
        loads.append(np.asarray(chosen[:, first:first + held].sum(0)))
    last, logprobs = _head(h, weights["norm_f"], weights["head"], tokens,
                           eps=eps, precision=precision)
    return (np.asarray(last), np.asarray(logprobs),
            np.stack(loads).astype(np.int32))
