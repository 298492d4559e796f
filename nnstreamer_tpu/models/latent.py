"""Parts the scoring decoders share (``models/glm_dsa.py``,
``models/longcat.py``, ``models/afmoe.py``, ``models/kimi_linear.py``,
``models/brumby.py``):
rotary embedding on interleaved pairs and the latent (MLA) projections
in the expanded form (the latent-attention decoders'), causal attention with the output
projection, a SwiGLU MLP, the sigmoid router, the scoring head, and the
frame and zoo wrappers of a scoring pass. A decoder's own file holds
what is its own: the layer's order, its projections, an indexer.

The configuration object a function takes is the caller's dataclass;
only the fields named in the function's docstring are read, by the HF
``config.json`` keys both architectures publish them under.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.sparse_attention import LANES, blocked_causal_attention
from ..tensors.info import TensorsInfo
from .transformer import rmsnorm

# how the work is cut, not what is computed
BLOCK_Q = 512             # queries an attention block
# rows a turn of the grouped expert product (ops/grouped.py): the chip's
# ridge, 256 rows x 2 FLOP over a weight's 2 bytes, so a tile's products
# about hide under the read of its expert's weights. What one expert is
# routed of a few thousand tokens is one tile or two, the last of them
# taken at 128 rows where 128 hold it (read on the chip, PERF.md PR 31:
# 512 rows multiply three rows of padding to each live one, 128 make a
# third more turns)
EXPERT_TILE = 256
# the columns a head's rotation is cut out at: a multiple of the lane
# width, so that cutting them out and putting them back shifts no lane
ROPE_ALIGN = 128


def rope_interleaved(x, positions, theta: float):
    """Rotary embedding over the last dim, pairs ``(2i, 2i+1)`` rotated
    together. ``x`` [S, ..., D], ``positions`` [S]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs       # [S, D/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_columns(x, positions, theta: float, nope: int, rope: int):
    """``x`` [..., S, D] with the ``rope`` columns from ``nope`` on
    rotated as :func:`rope_interleaved` rotates them and the others as
    they are (columns past ``nope + rope``, a head's padding, among
    them): what ``concatenate([x[..., :nope], rope(x[..., nope:nope +
    rope]), x[..., nope + rope:]])`` gives, to the bit. A pair's
    partner comes from a product with a constant 0 / +-1 matrix (one
    term a sum: exact) and not from a shuffle of lanes, and only the
    columns from the last multiple of ``ROPE_ALIGN`` at or before
    ``nope`` are read and written back; those of them outside the
    rotation meet cos 1 and sin 0."""
    d = x.shape[-1]
    cut = nope // ROPE_ALIGN * ROPE_ALIGN
    off, after = nope - cut, d - nope - rope
    freqs = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = jnp.repeat(positions.astype(jnp.float32)[:, None] * freqs, 2, -1)
    cos = jnp.pad(jnp.cos(ang), ((0, 0), (off, after)), constant_values=1.0)
    sin = jnp.pad(jnp.sin(ang), ((0, 0), (off, after)))
    # (a, b) -> (a cos - b sin, a sin + b cos): x cos + (x @ swap) sin
    swap = np.zeros((d - cut, d - cut), np.float32)
    for c in range(off, off + rope, 2):
        swap[c + 1, c], swap[c, c + 1] = -1.0, 1.0
    tail = x[..., cut:]
    partner = jnp.einsum("...d,de->...e", tail, jnp.asarray(swap, x.dtype),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    tail = tail.astype(jnp.float32) * cos + partner * sin
    return x.at[..., cut:].set(tail.astype(x.dtype))


def _mm(x, w):
    """Product accumulated in float32, handed on in the stream's dtype."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(x, p):
    """``(silu(x w1) * (x w3)) w2`` -> float32."""
    gate = jnp.dot(x, p["w1"], preferred_element_type=jnp.float32)
    up = jnp.dot(x, p["w3"], preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype), p["w2"],
                   preferred_element_type=jnp.float32)


def _mm_heads(x, w):
    """``x`` [S, r] by ``w`` [H, d, r] -> [H, S, d], accumulated in
    float32: head-major as the product writes it, which is how the
    attention kernel reads a head (``ops/sparse_attention.py``)."""
    return jnp.einsum("sr,hdr->hsd", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def head_major(w, head_dim: int):
    """A projection ``w`` [d, heads x head_dim] a head at a time with
    the contracted dimension last, ``[heads, head_dim, d]``
    (:func:`mla_weights`' layout, read on the chip the faster one), so
    that :func:`_mm_heads` writes its product head-major."""
    return jnp.transpose(w.reshape(w.shape[0], -1, head_dim), (1, 2, 0))


def qkv_heads(x, a, head_dim: int, eps: float):
    """The grouped-query projections of normed ``x`` [S, d] from an
    attention sublayer's leaves ``a`` (``wq`` [d, H x hd], ``wk``,
    ``wv`` [d, H_kv x hd], no bias; ``q_norm``, ``k_norm`` [hd]): ``q``
    [H, S, hd] and ``k`` [H_kv, S, hd] through an RMSNorm over the
    head's ``hd`` with a learned weight, ``v`` [H_kv, S, hd] as it is.
    Head-major, as the products write them and as a kernel reads a
    head; the weights' re-lay (:func:`head_major`) has no input in it,
    so the jax filter runs it once per load (``filters/prepare.py``)."""
    wq, wk, wv = (head_major(a[n], head_dim) for n in ("wq", "wk", "wv"))
    return (rmsnorm(_mm_heads(x, wq), a["q_norm"], eps),
            rmsnorm(_mm_heads(x, wk), a["k_norm"], eps), _mm_heads(x, wv))


def mla_weights(a, cfg):
    """What :func:`mla_qkv` multiplies the two latents by, from the
    attention sublayer's leaves ``a`` alone: ``(wq [H, wide, r_q], wk
    [H, wide, r], wv [H, v, r])``. ``wide`` is ``nope + rope`` rounded
    up to the attention kernel's lane width: a head's query rows and
    its key rows take zero rows up to it (the key's also where its
    roped part goes), so that q and k leave their products as the
    kernel reads them, with no pad between. The latent dimension is
    last: the array the TPU compiler otherwise copies each weight into
    for these products, and read on the chip the faster one (PERF.md,
    PR 33). No input is in it, so the jax filter runs it once per load
    (``filters/prepare.py``). A sublayer without a query latent
    (``q_lora_rank`` null: no ``wq_a``) has its one query projection
    under ``wq``, and ``r_q`` is the stream's width. Reads
    ``cfg.num_attention_heads``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim`` and ``kv_lora_rank``."""
    h, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    d = nope + cfg.qk_rope_head_dim
    wide = -(-d // LANES) * LANES

    def laid(w, width=None):
        # [r, H, n] -> [H, width, r]; lax.pad, which jnp.pad would wrap
        # in a program of its own that the load does not look into
        if width is not None and width > w.shape[2]:
            w = jax.lax.pad(w, np.zeros((), w.dtype), [
                (0, 0, 0), (0, 0, 0), (0, width - w.shape[2], 0)])
        return jnp.transpose(w, (1, 2, 0))

    w = a["wkv_b"].reshape(cfg.kv_lora_rank, h, -1)
    wq = a["wq_b"] if "wq_a" in a else a["wq"]
    return (laid(wq.reshape(-1, h, d), wide),
            laid(w[..., :nope], wide), laid(w[..., nope:]))


def _scaled(x, scale: float):
    """``x * scale`` with the factor kept in float32 (as a weak scalar
    it would be rounded to ``x``'s dtype first); 1 changes nothing."""
    if scale == 1.0:
        return x
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def mla_qkv(x, a, positions, cfg, *, q_scale: float = 1.0,
            kv_scale: float = 1.0, rope: bool = True):
    """The latent projections of normed ``x`` [S, d]: ``c_q`` [S, r_q]
    and per-head ``q`` [S, H, wide], ``k`` [S, H, wide] (the one roped
    key part repeated to every head), ``v`` [S, H, v].
    ``q_scale`` / ``kv_scale`` multiply the two latents after their
    norms (``mla_scale_q_lora`` / ``mla_scale_kv_lora``; the roped key
    part is not scaled). A sublayer without ``wq_a`` / ``q_norm`` has no
    query latent: its queries are projected from ``x`` itself, which is
    then what comes back as ``c_q``. ``rope`` False encodes no position
    (``mla_use_nope``): the key part every head shares and the queries'
    columns beside it go on as they are. Reads
    ``cfg.num_attention_heads``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``kv_lora_rank``,
    ``rms_norm_eps`` and ``rope_theta``.

    All three are views of head-major arrays, which no transpose or
    concatenation makes, and ``q`` and ``k`` are ``wide`` columns a
    head (:func:`mla_weights`), the ones past ``nope + rope`` zero:
    ``k`` and ``v`` are two products, and the roped key part is added
    into the gap the key's zero columns leave (one operand of each sum
    is zero: exact)."""
    nope, rp, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    wq, wk, wv = mla_weights(a, cfg)
    c_q = x
    if "wq_a" in a:
        c_q = _scaled(rmsnorm(_mm(x, a["wq_a"]), a["q_norm"],
                              cfg.rms_norm_eps), q_scale)
    q = _mm_heads(c_q, wq)
    if rope:
        q = rope_columns(q, positions, cfg.rope_theta, nope, rp)
    kv = _mm(x, a["wkv_a"])
    c_kv = _scaled(rmsnorm(kv[:, :r], a["kv_norm"], cfg.rms_norm_eps),
                   kv_scale)
    k_r = kv[:, r:]
    if rope:
        k_r = rope_interleaved(k_r, positions, cfg.rope_theta)
    k = _mm_heads(c_kv, wk)
    k = k + jnp.pad(k_r, ((0, 0), (nope, k.shape[-1] - nope - rp)))
    v = _mm_heads(c_kv, wv)
    return (c_q,) + tuple(jnp.transpose(t, (1, 0, 2)) for t in (q, k, v))


def causal_attention_out(
        q, k, v, wo, *, scale: float, block_q: int, scope: str,
        key_mask: Optional[Callable[[int, int],
                                    Optional[jax.Array]]] = None,
        window: Optional[int] = None, gate=None):
    """Softmax attention of ``q`` [S, H, Dk] over the keys at or before
    each query (``key_mask`` or a ``window`` may narrow them, ``k`` and
    ``v`` may have fewer heads: ``ops/sparse_attention.py``), scores
    times ``scale`` (the caller's, from the head's published size: a
    head may be padded), the result times ``sigmoid(gate)`` [S, H, Dv]
    where a gate is given, then the output projection ``wo`` [H * v,
    d]: -> [S, d] in ``q``'s dtype. ``scope`` names the operations in a
    trace."""
    o = blocked_causal_attention(
        q, k, v, scale=scale, block_q=block_q, key_mask=key_mask,
        window=window, scope=scope)
    with jax.named_scope(scope):
        if gate is not None:
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(o.dtype)
        # over (head, v) as the attention wrote them: no [S, H * v] copy
        wo = wo.reshape(o.shape[1], o.shape[2], -1)
        return jnp.einsum("shv,hvd->sd", o, wo,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)


def sigmoid_route(x, moe, top: int, scale: float):
    """``x`` [T, d] -> ``(choice int32 [T, top], weight float32 [T,
    top])`` over the whole router (``noaux_tc``, one group): ``s =
    sigmoid(x W_g)`` float32; the ``top`` largest of ``s + b`` are
    chosen (the bias moves the choice and not the weight); weights
    ``s_e / (sum over the chosen s + 1e-20) * scale``."""
    s = jax.nn.sigmoid(jnp.dot(x, moe["gate"],
                               preferred_element_type=jnp.float32))
    _, choice = jax.lax.top_k(s + moe["bias"].astype(jnp.float32), top)
    picked = jnp.take_along_axis(s, choice, axis=-1)
    weight = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * scale
    return choice.astype(jnp.int32), weight


def score(h, params, tokens, eps: float):
    """The head of a scoring pass over ``h`` [B, S, d] -> ``(last_logits
    float32 [B, V], logprobs float32 [B, S])``: the last position's
    logits, and at position t the log-probability of token t+1 (0 at
    S-1)."""
    with jax.named_scope("lm_head"):
        x = rmsnorm(h, params["norm_f"], eps)
        logits = jnp.dot(x, params["head"],
                         preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nxt = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)
        logprobs = jnp.pad(nxt[..., 0], ((0, 0), (0, 1)))
    return logits[:, -1], logprobs


def frame_model(forward, cfg, seq: int, load_dims: str):
    """``(apply_fn, in_info, out_info)`` for ``tensor_filter
    framework=jax``: one int32 ``[seq]`` token frame a buffer in, the
    three tensors of ``forward(params, tokens [1, seq], cfg)`` out, the
    load's dimensions as the caps spell them (innermost first)."""

    def apply_fn(p, tokens):
        last, logprobs, load = forward(p, tokens[None].astype(jnp.int32), cfg)
        return last[0], logprobs[0], load

    in_info = TensorsInfo.make("int32", str(seq))
    out_info = TensorsInfo.make("float32,float32,int32",
                                f"{cfg.vocab_size},{seq},{load_dims}")
    return apply_fn, in_info, out_info


def config_from_options(cls, zoo_name: str, dtype: str, sizes: dict):
    """A configuration dataclass from a zoo URI's options: any field of
    ``cls`` by its name, converted by the field's annotation."""
    kinds = {f.name: f.type for f in dataclasses.fields(cls)}
    unknown = sorted(set(sizes) - set(kinds))
    if unknown:
        raise ValueError(f"zoo://{zoo_name}: unknown option(s) {unknown}")
    convert = {"float": float, "int": int, "str": str,
               "bool": lambda v: v.lower() in ("1", "true")}
    return cls(dtype=jnp.dtype(dtype), **{
        k: convert[kinds[k]](v) for k, v in sizes.items()})
