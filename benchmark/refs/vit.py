"""Plain reference of the ViT configurations: the forward pass in
straightforward ``jax.numpy`` and float32, layer by layer, no kernels and
no batching layer. Imports nothing of the program; reads the benchmark's
own weights (``nnsbench.weights``) by the flax names the configuration
file's builder gave them.

Departures from Dosovitskiy et al., as the configuration lists them:
mean-pool over the patch tokens (no class token), LayerNorm eps 1e-6,
tanh-approximated GELU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .quant import make_dot, make_prep

EPS = 1e-6


def _layer_norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames=("patch", "precision"))
def _embed(frames, conv, pos, *, patch, precision):
    dot = make_dot(precision)
    x = frames.astype(jnp.float32) / 127.5 - 1.0
    b, h, w, c = x.shape
    x = x.reshape(b, h // patch, patch, w // patch, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, (h // patch) * (w // patch), patch * patch * c)
    kernel = conv["kernel"].reshape(patch * patch * c, -1)
    return dot(x, kernel) + conv["bias"] + pos[0]


@functools.partial(jax.jit, static_argnames=("precision",))
def _block(x, p, *, precision):
    dot = make_dot(precision)
    att = p["MultiHeadDotProductAttention_0"]
    d, heads, hd = att["query"]["kernel"].shape
    h = _layer_norm(x, p["LayerNorm_0"])

    def proj(name):
        w = att[name]["kernel"].reshape(d, heads * hd)
        y = dot(h, w) + att[name]["bias"].reshape(heads * hd)
        return y.reshape(*h.shape[:-1], heads, hd)

    prep = make_prep(precision)
    q, k, v = proj("query"), proj("key"), proj("value")
    scores = jnp.einsum("bqhd,bkhd->bhqk", prep(q), prep(k),
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    probs = jax.nn.softmax(scores, -1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", prep(probs), prep(v),
                     precision=jax.lax.Precision.HIGHEST)
    out = dot(ctx.reshape(*ctx.shape[:-2], heads * hd),
              att["out"]["kernel"].reshape(heads * hd, d))
    x = x + out + att["out"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"])
    h = dot(h, p["Dense_0"]["kernel"]) + p["Dense_0"]["bias"]
    h = jax.nn.gelu(h, approximate=True)
    return x + dot(h, p["Dense_1"]["kernel"]) + p["Dense_1"]["bias"]


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(x, ln, dense, *, precision):
    x = _layer_norm(x, ln).mean(axis=1)
    return make_dot(precision)(x, dense["kernel"]) + dense["bias"]


def forward(weights, frames, sizes: dict, precision: str = "f32",
            rows_per_block: int = 32):
    """uint8 frames [N, H, W, 3] -> float32 logits [N, classes], in
    blocks of rows so that it fits beside whatever else is resident."""
    import numpy as np
    p = weights["params"]
    outs = []
    for lo in range(0, frames.shape[0], rows_per_block):
        x = _embed(jnp.asarray(frames[lo:lo + rows_per_block]),
                   p["Conv_0"], p["pos_embed"], patch=sizes["patch_size"],
                   precision=precision)
        for i in range(sizes["num_hidden_layers"]):
            x = _block(x, p[f"EncoderBlock_{i}"], precision=precision)
        outs.append(np.asarray(_head(x, p["LayerNorm_0"], p["Dense_0"],
                                     precision=precision)))
    return np.concatenate(outs)
