"""Decoder whose every layer mixes tokens by a gated power retention and
carries a state from one buffer of a document to the next (the
``brumby`` architecture: Brumby-14B's ``config.json`` names every width
used here; the block is Qwen3's with the softmax taken out, Manifest AI,
"Scaling Context Requires Rethinking Attention", arXiv 2507.04239).

For one document ``h`` [T, d], position ``t`` counted from the
document's start, every layer pre-norm with a residual after each
sublayer (no bias in q, k, v, o):

    u      = RMSNorm(h; w_in)
    q_t,i  = RoPE(RMSNorm_hd(u_t W_q[i]; w_qn), t)      i < H    (half-split)
    k_t,j  = RoPE(RMSNorm_hd(u_t W_k[j]; w_kn), t)      j < H_kv
    v_t,j  = u_t W_v[j]
    g_t,j  = logsigmoid(u_t W_g[j] + b_j)   float32     G = running sum
    a_ts   = (q_t,i . k_s,j / hd)^2 * exp(G_t,j - G_s,j)    s <= t, j = i // (H / H_kv)
    o_t,i  = sum_s a_ts v_s,j / (sum_s a_ts + 1e-6)
    h      = h + concat_i(o_t,i) W_o
    h      = h + (silu(u' W_1) * (u' W_3)) W_2          u' = RMSNorm(h; w_post)

Stack: ``h0 = E[tokens]``, the layers, ``RMSNorm``, an untied head. The
mixer is ``ops/power_retention.py``: the weights are squares, so the sum
over the earlier tokens is a state ``(S, Z)`` a key/value head that the
next token updates (that module's docstring), and a buffer of ``S``
tokens is the document's positions ``position0 .. position0 + S - 1``
given the state its earlier buffers left. :func:`forward` takes that
state and returns the next one; **where ``position0`` is 0 the state it
was given is multiplied by zero inside the program**, so a document's
first buffer needs no event and no host branch.

``config.json`` has no key for the retention itself: the degree (2), the
gate (a scalar a key/value head through ``logsigmoid``, the state
decayed before the token is added), the normaliser (the plain sum of the
weights) and the chunk are this file's, ``retention_degree`` and
``retention_chunk`` in the configuration; ``benchmark/configs/
brumby_14b_pp4_l10.json`` lists each under ``assumed``.

Scope names: ``embed``, ``block/attn/retention`` (the mixer whole: norm,
projections, head norms, rotation, gate, the retention, the output
projection), ``nns_power_retention`` inside it (the kernel), ``block/mlp``,
``lm_head``.

Zoo entry ``zoo://brumby?...``: two tensors a frame in, ``tokens`` int32
``[S]`` and ``position0`` int32 ``[1]`` (the buffer's first position in
its document); two out, ``last_logits`` float32 ``[V]`` and ``logprobs``
float32 ``[S]`` (log-softmax of token t+1 at position t; 0 at S-1). The
builder hands the jax filter a fifth item, the state before a document,
which the filter keeps on the device between buffers
(``filters/jax_backend.py``). Options: any numeric field of
:class:`BrumbyConfig` by its name, ``seq``, ``seed``; the defaults are a
tiny model whose buffers of 64 tokens are four chunks.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.power_retention import phi_rows, power_retention
from ..tensors.info import TensorsInfo
from . import latent
from .latent import qkv_heads, swiglu
from .transformer import rmsnorm, rope


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """Field names are the HF ``config.json`` keys, and the two the
    retention adds. ``vocab_size`` is the slice of the vocabulary held
    here."""
    vocab_size: int = 64
    hidden_size: int = 64
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    intermediate_size: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    retention_degree: int = 2
    retention_chunk: int = 16
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "BrumbyConfig":
        """From a ``config.json`` dict; ``share``: ``dtype`` (a
        configuration file's own ``dtype`` is prose)."""
        names = {f.name for f in dataclasses.fields(cls)} - {"dtype"}
        return cls(**{**{k: v for k, v in hf.items() if k in names},
                      **share})

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the key/value heads do not divide the heads")
        if self.retention_degree != 2:
            raise ValueError("only the power retention of degree 2 is "
                             "built (ops/power_retention.py)")


def _init_params(cfg: BrumbyConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``; the gates' offsets spread the
    heads' memories from a few tokens to thousands."""
    d, hd = cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    w = latent.Seeded(key, cfg.dtype, d)
    # b = logit(exp(-1 / tau)), tau from 4 to 4096 tokens over the heads
    tau = jnp.exp(jnp.linspace(jnp.log(4.0), jnp.log(4096.0), hkv))
    keep = jnp.exp(-1.0 / tau)
    layers = [{
        "attn_norm": w.ones(d),
        "attn": {
            "wq": w.dense(d, h * hd), "wk": w.dense(d, hkv * hd),
            "wv": w.dense(d, hkv * hd), "wg": w.dense(d, hkv, gain=0.1),
            "bg": jnp.log(keep / (1.0 - keep)).astype(jnp.float32),
            "q_norm": w.ones(hd), "k_norm": w.ones(hd),
            "wo": w.dense(h * hd, d)},
        "ffn_norm": w.ones(d),
        "mlp": w.ffn(cfg.intermediate_size)}
        for _ in range(cfg.num_hidden_layers)]
    # the embedding's rows at unit variance: a token's identity leads its
    # stream (PERF.md, PR 38)
    return {"embed": w.dense(cfg.vocab_size, d, fan_in=1),
            "head": w.dense(d, cfg.vocab_size), "norm_f": w.ones(d),
            "layers": layers}


init_params = latent.jitted_init(_init_params)


def state_shapes(cfg: BrumbyConfig):
    """The carried state's tree: for each layer ``(S [H_kv, phi, hd], Z
    [H_kv, hd, hd])`` float32 (``ops/power_retention.py``), as
    ``jax.ShapeDtypeStruct``s."""
    hkv, hd = cfg.num_key_value_heads, cfg.head_dim
    one = (jax.ShapeDtypeStruct((hkv, phi_rows(hd), hd), jnp.float32),
           jax.ShapeDtypeStruct((hkv, hd, hd), jnp.float32))
    return tuple(one for _ in range(cfg.num_hidden_layers))


def zero_state(cfg: BrumbyConfig):
    """The state before a document, zeros on the host (the jax filter
    places it, and places it again after it dropped one)."""
    return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                        state_shapes(cfg))


def retain(h, layer, positions, state, cfg: BrumbyConfig):
    """The mixer's half of a layer over one buffer ``h`` [S, d] at
    ``positions`` [S], given the layer's state -> ``(h, state)``."""
    a, eps, hd = layer["attn"], cfg.rms_norm_eps, cfg.head_dim
    with jax.named_scope("block/attn/retention"):
        x = rmsnorm(h, layer["attn_norm"], eps)
        q, k, v = qkv_heads(x, a, hd, eps)              # head-major
        # [H, S, 1, hd]: the rotation's [S, heads, hd] with one head
        q, k = (rope(t[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
                for t in (q, k))
        gate = jax.nn.log_sigmoid(
            jnp.dot(x, a["wg"], preferred_element_type=jnp.float32)
            + a["bg"].astype(jnp.float32))              # [S, H_kv]
        o, state = power_retention(q, k, v, gate.T, state,
                                   chunk=cfg.retention_chunk)
        wo = a["wo"].reshape(o.shape[0], hd, -1)
        return h + jnp.einsum("hsv,hvd->sd", o, wo,
                              preferred_element_type=jnp.float32
                              ).astype(h.dtype), state


def forward(params, tokens, position0, state, cfg: BrumbyConfig):
    """One buffer of a document: ``tokens`` int32 [S] at positions
    ``position0 ..`` (int32 scalar), ``state`` as :func:`state_shapes`
    lays it out -> ``(last_logits float32 [V], logprobs float32 [S],
    state)``. ``position0`` 0 starts a document: the state given is not
    read."""
    tokens = tokens.astype(jnp.int32)
    position0 = jnp.asarray(position0, jnp.int32).reshape(())
    positions = position0 + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    carried = (position0 != 0).astype(jnp.float32)
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
    after = []
    for layer, held in zip(params["layers"], state):
        with jax.named_scope("block/attn/retention"):
            held = tuple(x * carried for x in held)
        h, held = retain(h, layer, positions, held, cfg)
        after.append(held)
        with jax.named_scope("block/mlp"):
            x = rmsnorm(h, layer["ffn_norm"], cfg.rms_norm_eps)
            h = h + swiglu(x, layer["mlp"]).astype(h.dtype)
    last, logprobs = latent.score(h[None], params, tokens[None],
                                  cfg.rms_norm_eps)
    return last[0], logprobs[0], tuple(after)


def frame_model(cfg: BrumbyConfig, seq: int):
    """``(apply_fn, in_info, out_info, state)`` for ``tensor_filter
    framework=jax``: ``apply_fn(params, state, tokens, position0) ->
    ((last_logits, logprobs), state)`` over one buffer of ``seq``
    tokens, and the state before a document."""

    def apply_fn(p, state, tokens, position0):
        last, logprobs, state = forward(p, tokens, position0, state, cfg)
        return (last, logprobs), state

    in_info = TensorsInfo.make("int32,int32", f"{seq},1")
    out_info = TensorsInfo.make("float32,float32", f"{cfg.vocab_size},{seq}")
    return apply_fn, in_info, out_info, zero_state(cfg)


latent.register("brumby", BrumbyConfig, init_params, frame_model)
