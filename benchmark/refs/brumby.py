"""Plain reference of the ``brumby`` configurations (Brumby-14B's block:
Qwen3's decoder layer with the softmax attention replaced by a gated
power retention of degree 2; every layer alike): the scoring pass over
one whole document in straightforward ``jax.numpy`` and float32 at
``highest`` precision, layer by layer. **The mixer is the quadratic
definition over the whole document**: every query against every earlier
key, the squared score times the gates' decay between the two, divided
by the sum of the weights. Never the chunked form, never a state, never
a kernel; no blocking beyond what fits it on the chip (the rows of the
projections, the MLP and the head go 4096 at a time, the queries 256 at
a time against the keys up to their block's end, one layer part's
weights are converted to float32 at a time). Imports nothing of the
program (the norm and the SwiGLU are ``refs/glm_dsa.py``'s, the same
plain functions); reads the benchmark's own weights by the names the
configuration file's builder gave them (embed, head, norm_f,
layers[i].{attn_norm, ffn_norm, attn.{wq, wk, wv, wg, bg, q_norm,
k_norm, wo}, mlp.{w1, w3, w2}}).

For one document ``h`` [T, d], position ``t`` from the document's
start, every layer ``h += Mixer(RMSNorm(h)); h += MLP(RMSNorm(h))``:

* **Mixer** (``H`` query heads on ``H_kv`` key/value heads of ``hd``,
  no bias): ``q_t,i = RoPE(RMSNorm_hd(u_t W_q[i]; w_qn), t)``, ``k_t,j``
  likewise, ``v_t,j = u_t W_v[j]`` (RoPE over the whole head,
  half-split pairs ``(c, c + hd / 2)``, ``rope_theta``); ``g_t,j =
  logsigmoid(u_t W_g[j] + b_j)``, ``G`` its running sum over the
  document (summed in float64); ``a_ts = (q_t,i . k_s,j / hd)^2 *
  exp(G_t,j - G_s,j)`` for ``s <= t``, ``j = i // (H / H_kv)``; ``o_t,i
  = sum_s a_ts v_s,j / (sum_s a_ts + 1e-6)``; ``concat_i(o) W_o``.
* **MLP**: ``(silu(u W_1) * (u W_3)) W_2``.
* No embedding scale; a last RMSNorm and an untied head.

``config.json`` has no key for the retention; what is built here is the
configuration file's ``assumed``: degree 2, the scalar gate a key/value
head through ``logsigmoid`` (the decay applies between two tokens, so a
token sees itself undecayed), the plain sum as normaliser with ``eps``
1e-6 beside scores over ``hd``, Qwen3's head norms and rotation kept,
no output gate (Manifest AI, arXiv 2507.04239; the ``retention``
package's ``power_retention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .glm_dsa import HI, _dense_mlp, _rms
from .quant import make_dot, make_prep

ROW_BLOCK = 4096        # rows of a projection, the MLP, the head; keys
QUERY_BLOCK = 256
EPS = 1e-6
MIXER = ("gate", "normalise", "rope", "qk_norm")    # the tests' switches


def _rope_half(x, pos, theta):
    """Pairs ``(c, c + D / 2)``; ``x`` [S, H, D] at positions ``pos``
    [S] float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos[:, None] * inv)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "precision", "rope", "qk_norm"))
def _project(h, pos0, p, *, heads, kv_heads, theta, eps, precision,
             rope=True, qk_norm=True):
    """Rows of one layer's ``q`` [S, H, hd], ``k``, ``v`` [S, H_kv, hd]
    and log-gates [S, H_kv] from rows of the stream that start at
    position ``pos0``."""
    dot, a = make_dot(precision), p["attn"]
    s = h.shape[0]
    x = _rms(h, p["attn_norm"], eps)
    q = dot(x, a["wq"]).reshape(s, heads, -1)
    k = dot(x, a["wk"]).reshape(s, kv_heads, -1)
    v = dot(x, a["wv"]).reshape(s, kv_heads, -1)
    if qk_norm:
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    if rope:
        pos = pos0 + jnp.arange(s, dtype=jnp.float32)
        q, k = _rope_half(q, pos, theta), _rope_half(k, pos, theta)
    g = jax.nn.log_sigmoid(dot(x, a["wg"]) + a["bg"].astype(jnp.float32))
    return q, k, v, g


@functools.partial(jax.jit, static_argnames=("precision", "normalise"))
def _retain(q, k, v, big_g, lo, *, precision, normalise=True):
    """The quadratic definition for the queries ``q`` [R, H, hd], rows
    ``lo ..`` of the document, against the keys ``k``, ``v`` [K, H_kv,
    hd] from the document's start (``K >= lo + R``), ``big_g`` [K, H_kv]
    the log-gates' running sum -> ``o`` [R, H x hd]. ``QUERY_BLOCK``
    queries at a time."""
    prep = make_prep(precision)
    r, heads, hd = q.shape
    keys, kv = k.shape[0], k.shape[1]
    kp, vp = prep(k), prep(v)
    at = jnp.arange(keys)
    qb = min(QUERY_BLOCK, r)
    if r % qb:
        raise ValueError(f"{r} queries are no multiple of {qb}")

    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, qb)
        rows = rows.reshape(qb, kv, heads // kv, hd)
        t = lo + start + jnp.arange(qb)
        g_t = jax.lax.dynamic_slice_in_dim(big_g, lo + start, qb)
        score = jnp.einsum("tjnd,sjd->jnts", prep(rows), kp,
                           precision=HI) / hd
        decay = jnp.exp(jnp.minimum(g_t.T[:, :, None] - big_g.T[:, None, :],
                                    0.0))                   # [kv, t, s]
        a = jnp.where((at[None, :] <= t[:, None])[None, None],
                      score * score * decay[:, None], 0.0)
        o = jnp.einsum("jnts,sjd->tjnd", prep(a), vp, precision=HI)
        if normalise:
            o = o / (jnp.sum(a, -1).transpose(2, 0, 1)[..., None] + EPS)
        return o.reshape(qb, heads * hd)

    return jax.lax.map(block, jnp.arange(0, r, qb)).reshape(
        r, heads * hd)


@functools.partial(jax.jit, static_argnames=("precision",))
def _project_out(h, o, wo, *, precision):
    return h + make_dot(precision)(o, wo)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_rows(h, norm, head, nxt, *, eps, precision):
    """Rows of the stream -> the last row's logits and each row's
    log-probability of the token after it (``nxt`` [rows])."""
    logits = make_dot(precision)(_rms(h, norm, eps), head)
    logp = jax.nn.log_softmax(logits, -1)
    return logits[-1], jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]


def _by_rows(fn, t):
    """``fn(lo, hi)`` over the document's rows, ``ROW_BLOCK`` at a time."""
    return [fn(lo, min(lo + ROW_BLOCK, t)) for lo in range(0, t, ROW_BLOCK)]


def forward(weights, tokens, sizes: dict, precision: str = "f32",
            buffer: int = 0, **without):
    """One int32 document [T] -> ``(last_logits float32 [T / buffer, V],
    logprobs float32 [T])`` as numpy: the logits at the last position
    of each ``buffer`` tokens (0: of the document) and at position t the
    log-probability of token t+1 (0 at T-1). ``without``: the tests'
    switches, each False removes a part that must change the result:
    ``gate`` (no decay), ``normalise``, ``rope``, ``qk_norm``."""
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    heads, kv = int(sizes["num_attention_heads"]), \
        int(sizes["num_key_value_heads"])
    unknown = set(without) - set(MIXER)
    if unknown:
        raise ValueError(f"unknown switch(es) {sorted(unknown)}")
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    buffer = buffer or t
    h = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for layer in weights["layers"]:
        part = {k: layer[k] for k in ("attn_norm", "attn")}
        q, k, v, g = (jnp.concatenate(x) for x in zip(*_by_rows(
            lambda lo, hi: _project(
                h[lo:hi], jnp.float32(lo), part, heads=heads, kv_heads=kv,
                theta=theta, eps=eps, precision=precision,
                rope=without.get("rope", True),
                qk_norm=without.get("qk_norm", True)), t)))
        if not without.get("gate", True):
            g = jnp.zeros_like(g)
        big_g = jnp.asarray(np.cumsum(np.asarray(g, np.float64), 0),
                            jnp.float32)
        o = jnp.concatenate(_by_rows(
            lambda lo, hi: _retain(
                q[lo:hi], k[:hi], v[:hi], big_g[:hi], lo,
                precision=precision,
                normalise=without.get("normalise", True)), t))
        h = jnp.concatenate(_by_rows(
            lambda lo, hi: _dense_mlp(
                _project_out(h[lo:hi], o[lo:hi], layer["attn"]["wo"],
                             precision=precision),
                layer["ffn_norm"], layer["mlp"], eps=eps,
                precision=precision), t))
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    last, logprobs = [], []
    for lo in range(0, t, buffer):
        rows, lp = _head_rows(h[lo:lo + buffer], weights["norm_f"],
                              weights["head"], nxt[lo:lo + buffer], eps=eps,
                              precision=precision)
        last.append(np.asarray(rows))
        logprobs.append(np.asarray(lp))
    logprobs = np.concatenate(logprobs)
    logprobs[-1] = 0.0
    return np.stack(last), logprobs
