"""Causal attention under a learned per-query key selection, in plain XLA.

Two device ops a sparse-attention decoder needs and the dense paths do
not have:

* :func:`topk_mask` - the exact set of the ``k`` largest scores of each
  row among its valid entries (all of them where a row has fewer), ties
  to the lower index. The k-th value is found by a bitwise search over
  the scores' sortable integer form: 32 compare-and-count passes over
  the row block, no sort and no ``approx_max_k``; what it selects is
  the set ``lax.top_k`` would, which the tests hold it to.
* :func:`blocked_causal_attention` - softmax attention of query blocks
  over the keys at or before them, so that the ``[heads, S, S]`` scores
  never exist at once (``models/transformer._dense_attention`` builds
  them whole; ``ops/attention.py`` is non-causal and VMEM-bound to
  S ~ 1024). A block's keys stop at its last query, which skips the
  upper triangle block-wise; inside the block the mask does the rest.
  ``key_mask(lo, hi)`` may narrow each query's keys further (the
  indexer's selection); masked pairs are computed and discarded, not
  gathered away - the gathered form is the long-context one (ROADMAP).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order
    (-0.0 and 0.0 made one value first)."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_mask(scores, k: int, valid):
    """``scores`` [T, S] float32, ``valid`` [T, S] bool -> bool [T, S]:
    each row's ``k`` largest valid entries, every valid entry of a row
    that has at most ``k``; among equal scores the lower index wins."""
    # invalid entries take key 0, under every float but a negative NaN
    keys = jnp.where(valid, _sortable(scores), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        cnt = jnp.sum(keys >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(cnt >= k, cand, thr)

    # the largest threshold that still leaves k entries at or above it:
    # the k-th largest key itself (0 where the row has fewer than k)
    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(keys.shape[:1], jnp.uint32))[:, None]
    above = keys > thr
    tied = keys == thr
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32, keepdims=True)
    first = jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room
    return (above | (tied & first)) & valid


def blocked_causal_attention(
        q, k, v, *, scale: float, block_q: int,
        key_mask: Optional[Callable[[int, int], Optional[jax.Array]]] = None,
        scope: Optional[str] = None):
    """``q`` [S, H, Dk], ``k`` [S, H, Dk], ``v`` [S, H, Dv] -> [S, H, Dv].

    Query block ``[lo, hi)`` attends keys ``[0, hi)`` with ``s <= t``;
    ``key_mask(lo, hi)`` returns bool ``[hi - lo, hi]`` (True = keep) or
    None for a block it leaves causal; every query must keep at least
    one key. Scores and softmax in float32, the two products accumulate
    in float32. ``scope`` names this
    function's own operations in a trace (``jax.named_scope``); the
    mask's are named by whoever computes it."""
    s = q.shape[0]

    def named():
        return jax.named_scope(scope) if scope else contextlib.nullcontext()

    outs = []
    for lo in range(0, s, block_q):
        hi = min(lo + block_q, s)
        extra = key_mask(lo, hi) if key_mask is not None else None
        with named():
            keep = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
            if extra is not None:
                keep = keep & extra
            scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi],
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(keep[None], scores, -jnp.inf)
            # softmax with the division after the second product: the
            # weights (a row's largest is 1) go to the product as they
            # are, and its [q, h, d] result is divided by the row sums,
            # which saves a pass over the [h, q, k] block
            weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
            total = jnp.sum(weights, -1)
            out = jnp.einsum("hqk,khd->qhd", weights.astype(v.dtype), v[:hi],
                             preferred_element_type=jnp.float32)
            outs.append((out / total.T[:, :, None]).astype(v.dtype))
    with named():
        return jnp.concatenate(outs) if len(outs) > 1 else outs[0]
