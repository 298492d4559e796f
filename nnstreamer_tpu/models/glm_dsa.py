"""Decoder with latent attention, a learned sparse-attention indexer and a
sigmoid-routed expert layer that is told which experts it holds (the
``glm_moe_dsa`` architecture: GLM-5's ``config.json`` names every size
used here).

A layer, for one sequence ``x`` [S, d] (prefill, the expanded form; the
absorbed form belongs to a decode path and is not built here):

* **MLA.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` per head
  ``(nope | rope)``, RoPE (interleaved pairs) on the rope part;
  ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, RoPE on ``k_r``,
  one for all heads; ``[k_nope | v] = c_kv W_kvb`` per head; scores
  ``q.[k_nope | k_r] / sqrt(nope + rope)`` over keys ``s <= t`` that the
  indexer selected, float32 softmax, ``o = (P v) W_o``.
* **Indexer.** ``q_I = c_q W_Iq`` (heads x dim), ``k_I = LayerNorm(x
  W_Ik)`` (one a token), RoPE on the first ``rope`` of each; ``I[t,s] =
  sum_h w[t,h] ReLU(q_I[t,h].k_I[s])`` with ``w = x W_Iw / sqrt(heads x
  dim)``, float32; ``sel(t)`` = the ``index_topk`` largest ``I[t, s<=t]``,
  all of them while ``t < index_topk``, ties to the lower index
  (``ops/sparse_attention.py``).
* **Router** (``noaux_tc``, one group). ``s = sigmoid(x W_g)`` float32
  over the whole router; the ``top`` largest of ``s + b`` are chosen;
  weights ``s_e / (sum_chosen s + 1e-20) x routed_scaling_factor``, the
  sum over every chosen expert, held or not. The layer's output is ``x
  + shared(x) + sum_{e chosen and held} w_e expert_e(x)``: it is given
  ``(n_routed_experts, held_first, held_count)``, routes over all and
  computes its own experts' part (``ops/grouped.py``); what the experts
  of other chips would add is not stood in for.

The first ``first_k_dense_replace`` layers have a dense SwiGLU MLP in the
expert layer's place. Scope names: ``embed``, ``block/attn``,
``block/indexer`` (``block/indexer/select`` inside), ``block/moe/route``,
``block/moe/shared``, ``block/moe/experts``, ``block/mlp``, ``lm_head``.

Zoo entry ``zoo://glm_dsa?...``: int32 token frame ``[S]`` -> three
tensors, ``last_logits`` float32 ``[V]``, ``logprobs`` float32 ``[S]``
(log-softmax of token t+1 at position t; 0 at S-1) and ``expert_load``
int32 ``[moe layers, held]`` (token-expert pairs each held expert
served). The multi-token-prediction layer does nothing on a scoring
pass and is not loaded.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.grouped import group_by_expert, grouped_swiglu
from ..ops.sparse_attention import blocked_causal_attention, topk_mask
from ..tensors.info import TensorsInfo
from .transformer import rmsnorm
from .zoo import register_model

LAYER_NORM_EPS = 1e-6     # the indexer's key norm
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


@dataclasses.dataclass(frozen=True)
class GLMDSAConfig:
    """Field names are the HF ``config.json`` keys. ``n_routed_experts``
    is the router's width; ``held_first`` / ``held_count`` say which of
    them this chip holds (0 held = all of them). ``vocab_size`` is the
    slice of the vocabulary held here."""
    vocab_size: int = 64
    hidden_size: int = 64
    num_hidden_layers: int = 3
    first_k_dense_replace: int = 1
    num_attention_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 12
    qk_rope_head_dim: int = 4
    v_head_dim: int = 16
    index_n_heads: int = 2
    index_head_dim: int = 8
    index_topk: int = 16
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    n_routed_experts: int = 32
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    held_first: int = 0
    held_count: int = 0
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **share) -> "GLMDSAConfig":
        """From a ``config.json`` dict (``rope_theta`` flat or under
        ``rope_parameters``); ``share``: ``held_first``, ``held_count``
        and ``dtype``."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.setdefault("rope_theta", (hf.get("rope_parameters") or {}).get(
            "rope_theta", cls.rope_theta))
        return cls(**{**kw, **share})

    @property
    def held(self) -> int:
        return self.held_count or self.n_routed_experts

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    def __post_init__(self):
        if self.held_first < 0 or \
                self.held_first + self.held > self.n_routed_experts:
            raise ValueError("held experts lie outside the router")
        if self.n_moe_layers < 1 or self.first_k_dense_replace < 0:
            raise ValueError("glm_dsa needs at least one expert layer")


def init_params(cfg: GLMDSAConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded tree in ``cfg.dtype``; the router's bias small and not
    zero, so that it changes choices."""
    return jax.jit(_init_params, static_argnums=0)(cfg, key)


def _init_params(cfg: GLMDSAConfig, key):
    dt, d, h = cfg.dtype, cfg.hidden_size, cfg.num_attention_heads
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    counter = itertools.count()

    def dense(*shape, fan_in=None):
        k = jax.random.fold_in(key, next(counter))
        scale = (fan_in or shape[-2]) ** -0.5
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def ones(n):
        return jnp.ones((n,), dt)

    def ffn(width, *lead):
        return {"w1": dense(*lead, d, width), "w3": dense(*lead, d, width),
                "w2": dense(*lead, width, d)}

    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {
            "attn_norm": ones(d),
            "attn": {
                "wq_a": dense(d, cfg.q_lora_rank),
                "q_norm": ones(cfg.q_lora_rank),
                "wq_b": dense(cfg.q_lora_rank, h * (nope + rp)),
                "wkv_a": dense(d, cfg.kv_lora_rank + rp),
                "kv_norm": ones(cfg.kv_lora_rank),
                "wkv_b": dense(cfg.kv_lora_rank, h * (nope + vd)),
                "wo": dense(h * vd, d)},
            "indexer": {
                "wq_b": dense(cfg.q_lora_rank,
                              cfg.index_n_heads * cfg.index_head_dim),
                "wk": dense(d, cfg.index_head_dim),
                "k_norm_w": ones(cfg.index_head_dim),
                "k_norm_b": jnp.zeros((cfg.index_head_dim,), dt),
                "w_proj": dense(d, cfg.index_n_heads)},
            "ffn_norm": ones(d)}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = ffn(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "gate": dense(d, cfg.n_routed_experts),
                "bias": 0.02 * dense(cfg.n_routed_experts, fan_in=1),
                "shared": ffn(cfg.n_shared_experts
                              * cfg.moe_intermediate_size),
                "experts": ffn(cfg.moe_intermediate_size, cfg.held)}
        layers.append(layer)
    return {"embed": dense(cfg.vocab_size, d, fan_in=d),
            "head": dense(d, cfg.vocab_size), "norm_f": ones(d),
            "layers": layers}


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


def rope_columns(x, positions, theta: float, nope: int):
    """``x`` [..., S, D] with the columns from ``nope`` on rotated as
    :func:`rope_interleaved` rotates them and the others as they are:
    what ``concatenate([x[..., :nope], rope(x[..., nope:])])`` gives,
    to the bit. A pair's partner comes from a product with a constant
    0 / +-1 matrix (one term a sum: exact) and not from a shuffle of
    lanes, and only the columns from the last multiple of
    ``ROPE_ALIGN`` at or before ``nope`` are read and written back."""
    d = x.shape[-1]
    cut = nope // ROPE_ALIGN * ROPE_ALIGN
    rp, off = d - nope, nope - cut
    freqs = theta ** (-jnp.arange(0, rp, 2, dtype=jnp.float32) / rp)
    ang = jnp.repeat(positions.astype(jnp.float32)[:, None] * freqs, 2, -1)
    cos = jnp.pad(jnp.cos(ang), ((0, 0), (off, 0)), constant_values=1.0)
    sin = jnp.pad(jnp.sin(ang), ((0, 0), (off, 0)))
    # (a, b) -> (a cos - b sin, a sin + b cos): x cos + (x @ swap) sin
    swap = np.zeros((d - cut, d - cut), np.float32)
    for c in range(off, d - cut, 2):
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
    """``x`` [S, r] by ``w`` [r, H, d] -> [H, S, d], accumulated in
    float32: head-major as the product writes it, which is how the
    attention kernel reads a head (``ops/sparse_attention.py``)."""
    return jnp.einsum("sr,rhd->hsd", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def mla_qkv(x, a, positions, cfg: GLMDSAConfig):
    """The latent projections of normed ``x`` [S, d]: ``c_q`` [S, r_q]
    and per-head ``q`` [S, H, nope+rope], ``k`` [S, H, nope+rope] (the
    one roped key part repeated to every head), ``v`` [S, H, v].

    All three are views of head-major arrays, which no transpose or
    concatenation makes: ``k`` and ``v`` are two products; the key
    columns of ``wkv_b`` take ``rope`` columns of zeros a head, and the
    roped part is added into the gap they leave (one operand of each
    sum is zero: exact)."""
    h, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    r, rp = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    c_q = rmsnorm(_mm(x, a["wq_a"]), a["q_norm"], cfg.rms_norm_eps)
    q = rope_columns(_mm_heads(c_q, a["wq_b"].reshape(-1, h, nope + rp)),
                     positions, cfg.rope_theta, nope)
    kv = _mm(x, a["wkv_a"])
    c_kv = rmsnorm(kv[:, :r], a["kv_norm"], cfg.rms_norm_eps)
    k_r = rope_interleaved(kv[:, r:], positions, cfg.rope_theta)
    w = a["wkv_b"].reshape(r, h, -1)
    k = _mm_heads(c_kv, jnp.pad(w[..., :nope], ((0, 0), (0, 0), (0, rp)))) \
        + jnp.pad(k_r, ((0, 0), (nope, 0)))
    v = _mm_heads(c_kv, w[..., nope:])
    return (c_q,) + tuple(jnp.transpose(t, (1, 0, 2)) for t in (q, k, v))


def indexer_qkw(x, c_q, ix, positions, cfg: GLMDSAConfig):
    """The indexer's projections: ``q_I`` [S, heads, dim], ``k_I`` [S,
    dim] (LayerNorm with bias), ``w`` float32 [S, heads], scaled."""
    s, hi, di = x.shape[0], cfg.index_n_heads, cfg.index_head_dim
    rp = cfg.qk_rope_head_dim

    def roped(t):
        return jnp.concatenate([rope_interleaved(
            t[..., :rp], positions, cfg.rope_theta), t[..., rp:]], -1)

    q_i = roped(_mm(c_q, ix["wq_b"]).reshape(s, hi, di))
    k = jnp.dot(x, ix["wk"], preferred_element_type=jnp.float32)
    mu = jnp.mean(k, -1, keepdims=True)
    var = jnp.mean(jnp.square(k - mu), -1, keepdims=True)
    k = ((k - mu) * jax.lax.rsqrt(var + LAYER_NORM_EPS)).astype(x.dtype) \
        * ix["k_norm_w"] + ix["k_norm_b"]
    w = jnp.dot(x, ix["w_proj"], preferred_element_type=jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    return q_i, roped(k), w


def index_scores(q_i, k_i, w):
    """``I[t, s] = sum_h w[t, h] ReLU(q_I[t, h] . k_I[s])``, float32."""
    dots = jnp.einsum("qhd,kd->qhk", q_i, k_i,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)


def attend(h, layer, cfg: GLMDSAConfig):
    """The attention half of a layer for one sequence ``h`` [S, d]."""
    s = h.shape[0]
    positions = jnp.arange(s, dtype=jnp.int32)
    with jax.named_scope("block/attn"):
        x = rmsnorm(h, layer["attn_norm"], cfg.rms_norm_eps)
        c_q, q, k, v = mla_qkv(x, layer["attn"], positions, cfg)
    with jax.named_scope("block/indexer"):
        q_i, k_i, w = indexer_qkw(x, c_q, layer["indexer"], positions, cfg)

    def selected(lo, hi):
        # up to index_topk keys lie at or before these queries: all kept
        if hi <= cfg.index_topk:
            return None
        with jax.named_scope("block/indexer"):
            scores = index_scores(q_i[lo:hi], k_i[:hi], w[lo:hi])
            with jax.named_scope("select"):
                causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None]
                return topk_mask(scores, cfg.index_topk, causal)

    o = blocked_causal_attention(
        q, k, v, scale=q.shape[-1] ** -0.5, block_q=BLOCK_Q,
        key_mask=selected, scope="block/attn")
    with jax.named_scope("block/attn"):
        # over (head, v) as the attention wrote them: no [S, H * v] copy
        wo = layer["attn"]["wo"].reshape(o.shape[1], o.shape[2], -1)
        return h + jnp.einsum("shv,hvd->sd", o, wo,
                              preferred_element_type=jnp.float32
                              ).astype(h.dtype)


def route(x, moe, cfg: GLMDSAConfig):
    """``x`` [T, d] -> ``(choice int32 [T, top], weight float32 [T,
    top])`` over the whole router. The bias moves the choice and not
    the weight."""
    s = jax.nn.sigmoid(jnp.dot(x, moe["gate"],
                               preferred_element_type=jnp.float32))
    _, choice = jax.lax.top_k(s + moe["bias"].astype(jnp.float32),
                              cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(s, choice, axis=-1)
    weight = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling_factor
    return choice.astype(jnp.int32), weight


def moe_ffn(h, layer, cfg: GLMDSAConfig):
    """The expert half of a layer over tokens ``h`` [T, d] ->
    ``(h', load)``: shared expert plus this chip's routed experts'
    part, and the pairs each held expert served, int32 [held]."""
    moe = layer["moe"]
    with jax.named_scope("block/moe/route"):
        x = rmsnorm(h, layer["ffn_norm"], cfg.rms_norm_eps)
        choice, weight = route(x, moe, cfg)
        order, load = group_by_expert(choice, cfg.held_first, cfg.held)
    with jax.named_scope("block/moe/shared"):
        out = swiglu(x, moe["shared"])
    with jax.named_scope("block/moe/experts"):
        e = moe["experts"]
        out = out + grouped_swiglu(x, order, load, weight, e["w1"], e["w3"],
                                   e["w2"], tile=EXPERT_TILE)
        return h + out.astype(h.dtype), load


def forward(params, tokens, cfg: GLMDSAConfig):
    """``tokens`` int32 [B, S] -> ``(last_logits float32 [B, V],
    logprobs float32 [B, S], expert_load int32 [moe layers, held])``;
    the load is summed over the batch."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
    loads = []
    for layer in params["layers"]:
        h = jnp.stack([attend(h[j], layer, cfg) for j in range(b)])
        flat = h.reshape(b * s, -1)
        if "moe" in layer:
            flat, load = moe_ffn(flat, layer, cfg)
            loads.append(load)
        else:
            with jax.named_scope("block/mlp"):
                x = rmsnorm(flat, layer["ffn_norm"], cfg.rms_norm_eps)
                flat = flat + swiglu(x, layer["mlp"]).astype(flat.dtype)
        h = flat.reshape(b, s, -1)
    with jax.named_scope("lm_head"):
        x = rmsnorm(h, params["norm_f"], cfg.rms_norm_eps)
        logits = jnp.dot(x, params["head"],
                         preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nxt = jnp.take_along_axis(logp[:, :-1], tokens[:, 1:, None], axis=-1)
        logprobs = jnp.pad(nxt[..., 0], ((0, 0), (0, 1)))
    return logits[:, -1], logprobs, jnp.stack(loads)


def frame_model(cfg: GLMDSAConfig, seq: int):
    """``(apply_fn, in_info, out_info)`` for ``tensor_filter
    framework=jax``: one int32 ``[seq]`` token frame a buffer in, the
    three tensors of :func:`forward` out."""

    def apply_fn(p, tokens):
        last, logprobs, load = forward(p, tokens[None].astype(jnp.int32), cfg)
        return last[0], logprobs[0], load

    in_info = TensorsInfo.make("int32", str(seq))
    out_info = TensorsInfo.make(
        "float32,float32,int32",
        f"{cfg.vocab_size},{seq},{cfg.held}:{cfg.n_moe_layers}")
    return apply_fn, in_info, out_info


@register_model("glm_dsa")
def _build_glm_dsa(seq: str = "64", seed: str = "0", dtype: str = "bfloat16",
                   **sizes: str):
    """``zoo://glm_dsa?seq=64&hidden_size=64&held_count=8&...``: any
    field of :class:`GLMDSAConfig` by its name; the defaults are a tiny
    model whose sparse regime is live at ``seq`` 64."""
    kinds = {f.name: f.type for f in dataclasses.fields(GLMDSAConfig)}
    unknown = sorted(set(sizes) - set(kinds))
    if unknown:
        raise ValueError(f"zoo://glm_dsa: unknown option(s) {unknown}")
    cfg = GLMDSAConfig(dtype=jnp.dtype(dtype), **{
        k: float(v) if kinds[k] == "float" else int(v)
        for k, v in sizes.items()})
    params = init_params(cfg, jax.random.PRNGKey(int(seed)))
    apply_fn, in_info, out_info = frame_model(cfg, int(seq))
    return apply_fn, params, in_info, out_info
