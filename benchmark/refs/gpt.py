"""Plain reference of the decoder configurations: the full causal forward
pass over a prompt with its served tokens, in straightforward
``jax.numpy`` and float32, layer by layer: no cache, no paging, no
chunking, no batching. Imports nothing of the program; reads the
benchmark's own weights by the names the configuration file gave them
(embed, head, ln_f, layers[i].{ln1,wq,wk,wv,wo,ln2,w1,w3,w2}).

The block is the published one: RMSNorm, rotary positions over half
pairs (rotate_half), causal softmax attention over all heads, SwiGLU,
untied head."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .quant import make_dot, make_prep


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit,
                   static_argnames=("heads", "theta", "eps", "precision"))
def _block(h, p, *, heads, theta, eps, precision):
    dot = make_dot(precision)
    s, d = h.shape
    hd = d // heads
    x = _rms(h, p["ln1"], eps)
    q = _rope(dot(x, p["wq"]).reshape(s, heads, hd), theta)
    k = _rope(dot(x, p["wk"]).reshape(s, heads, hd), theta)
    v = dot(x, p["wv"]).reshape(s, heads, hd)
    prep = make_prep(precision)
    scores = jnp.einsum("qhd,khd->hqk", prep(q), prep(k),
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    ctx = jnp.einsum("hqk,khd->qhd", prep(probs), prep(v),
                     precision=jax.lax.Precision.HIGHEST)
    h = h + dot(ctx.reshape(s, d), p["wo"])
    x = _rms(h, p["ln2"], eps)
    ff = jax.nn.silu(dot(x, p["w1"])) * dot(x, p["w3"])
    return h + dot(ff, p["w2"])


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, ln_f, head, *, eps, precision):
    return make_dot(precision)(_rms(h, ln_f, eps), head)


def logits_at(weights, tokens, positions, sizes: dict,
              precision: str = "f32"):
    """``tokens``: one sequence [S] (prompt + served tokens but the last,
    padded on the right to a shared length if the caller wishes: causal,
    so padding changes nothing before it). Returns float32 logits
    [len(positions), vocab] of the given positions."""
    import numpy as np
    h = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    for layer in weights["layers"]:
        h = _block(h, layer, heads=sizes["num_attention_heads"],
                   theta=float(sizes["rope_theta"]),
                   eps=float(sizes["rms_norm_eps"]), precision=precision)
    return np.asarray(_head(h[jnp.asarray(positions)], weights["ln_f"],
                            weights["head"],
                            eps=float(sizes["rms_norm_eps"]),
                            precision=precision))
