"""Causal attention under a learned per-query key selection.

Two device ops a sparse-attention decoder needs and the dense paths do
not have:

* :func:`topk_mask` - the exact set of the ``k`` largest scores of each
  row among its valid entries (all of them where a row has fewer), ties
  to the lower index. The k-th value is found by a bitwise search over
  the scores' sortable integer form: 32 compare-and-count passes over
  the row block, no sort and no ``approx_max_k``; what it selects is
  the set ``lax.top_k`` would, which the tests hold it to. Plain XLA.
* :func:`blocked_causal_attention` - softmax attention of query blocks
  over the keys at or before them. The inside of a block (``q.k``,
  scale, mask, softmax, ``p.v``, the division) is one Pallas TPU kernel,
  ``nns_masked_attention``, that walks the key tiles with a running
  maximum and sum in VMEM: neither the scores nor the weights exist in
  HBM (``models/transformer._dense_attention`` builds ``[heads, S, S]``
  whole; ``ops/attention.py`` is non-causal and holds a whole score
  block in VMEM, so it stops at S ~ 1024). Key tiles that lie wholly
  after a query tile are not visited; inside the tile on the diagonal
  the mask does the rest. A ``window`` keeps keys ``t - window < s <=
  t`` only: a query tile's walk then starts at the first key tile its
  window reaches (the tiles wholly behind it are no grid step at all),
  the tiles at the two edges are masked from iotas and those between
  run the unmasked body. ``k`` and ``v`` may have fewer heads than
  ``q`` (grouped queries): query head ``h`` reads key/value head ``h
  // (H / H_kv)``, whose tiles the index map names, so nothing is
  repeated in HBM. ``key_mask(lo, hi)`` may narrow each query's
  keys further (the indexer's selection), as one int8 tile set shared
  by all heads: a dropped pair inside a visited tile is computed and
  discarded, not gathered away - the gathered form is the long-context
  one (ROADMAP). The same kernel body runs everywhere: compiled by
  Mosaic on a TPU, through the Pallas interpreter elsewhere (how the
  CPU tests run it). :func:`reference_blocked_attention` is the block
  in plain XLA: the tests' oracle, never a stand-in.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .attention import _round_up


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


def reference_blocked_attention(
        q, k, v, *, scale: float, block_q: int,
        key_mask: Optional[Callable[[int, int], Optional[jax.Array]]] = None,
        window: Optional[int] = None):
    """:func:`blocked_causal_attention` in plain XLA, a block's
    ``[H, block, hi]`` float32 scores and weights passing through HBM
    and a shared key/value head repeated for each of its query heads:
    the parity oracle of the tests."""
    s = q.shape[0]
    k, v = (jnp.repeat(x, q.shape[1] // x.shape[1], axis=1) for x in (k, v))
    outs = []
    for lo in range(0, s, block_q):
        hi = min(lo + block_q, s)
        ahead = jnp.arange(lo, hi)[:, None] - jnp.arange(hi)[None, :]
        keep = ahead >= 0
        if window is not None:
            keep &= ahead < window
        extra = key_mask(lo, hi) if key_mask is not None else None
        if extra is not None:
            keep = keep & extra
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi],
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(keep[None], scores, -jnp.inf)
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        total = jnp.sum(weights, -1)
        out = jnp.einsum("hqk,khd->qhd", weights.astype(v.dtype), v[:hi],
                         preferred_element_type=jnp.float32)
        outs.append((out / total.T[:, :, None]).astype(v.dtype))
    return jnp.concatenate(outs)


# how the work is cut, not what is computed: the kernel's query and key
# tiles (rows of a block a grid step, keys a step of the running softmax)
# and the heads a grid step takes, which share its mask tile. Read on the
# chip at 64 heads of 256 (PERF.md, PR 29): smaller tiles are slower,
# 1024 keys no faster; 4 heads a step are 0.2 ms a layer faster than 2
# but need more than the 16 MB of VMEM a kernel gets unasked, and asking
# for more takes as much from the buffers XLA keeps there between
# kernels (the lm block's expert loop lost 11 ms a sequence to it)
TILE_Q = 512
TILE_K = 512
HEADS_PER_STEP = 2
LANES = 128
# a dropped pair's score: finite, so a tile that keeps nothing of a row
# leaves exp(0) and not exp(nan) behind, and what it leaves is scaled by
# exp(DROPPED - m) = 0 as soon as the row meets a key it keeps
DROPPED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _first_tile(row0, window: Optional[int], tk: int):
    """The first key tile that a query tile starting at row ``row0``
    walks: tile 0, or under a ``window`` the tile of the first key its
    first query keeps. Python ints in, an int out; a traced row in the
    kernel and the index maps."""
    if window is None:
        return 0
    behind = row0 - window + 1
    return (max(behind, 0) if isinstance(behind, int)
            else jnp.maximum(behind, 0)) // tk


def _attention_kernel(*refs, scale: float, lo: int, tq: int, tk: int,
                      masked: bool, window: Optional[int]):
    """Grid ``(head group, query tile, key tile)``, the key tiles in
    turn from the query tile's first (:func:`_first_tile`): one step of
    the running softmax of ``tq`` queries over ``tk`` keys, for each
    head of the group (the heads of a group that share a key/value head
    read the one tile held). The running maximum and sum are
    kept across all lanes (``[tq, LANES]``), so that taking them off a
    score tile repeats whole registers and broadcasts no lane."""
    from jax.experimental import pallas as pl

    q_ref, k_ref, v_ref = refs[:3]
    keep_ref = refs[3] if masked else None
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    i, j = pl.program_id(1), pl.program_id(2)
    row0 = lo + i * tq          # the tile's first query, as a key index
    # ... and this step's first key: the walk starts where the window does
    col0 = j * tk if window is None \
        else (_first_tile(row0, window, tk) + j) * tk
    group, _, dv = acc_ref.shape
    shared = group // k_ref.shape[0]    # query heads a key/value head

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, DROPPED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(keep):
        for g in range(group):
            scores = jax.lax.dot_general(
                q_ref[g], k_ref[g // shared], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if keep is not None:
                scores = jnp.where(keep, scores, DROPPED)
            m_prev = m_ref[g]
            m_next = jnp.maximum(m_prev, jnp.max(scores, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            weights = jnp.exp(scores - jnp.tile(m_next, (1, tk // LANES)))
            m_ref[g] = m_next
            l_ref[g] = alpha * l_ref[g] + jnp.sum(weights, -1, keepdims=True)
            acc_ref[g] = jnp.tile(alpha, (1, dv // LANES)) * acc_ref[g] \
                + jnp.dot(weights.astype(v_ref.dtype), v_ref[g // shared],
                          preferred_element_type=jnp.float32)

    # a key tile wholly after the tile's last query is not visited
    visited = col0 <= row0 + tq - 1
    if masked:
        pl.when(visited)(lambda: step(keep_ref[...] != 0))
    else:
        # only a tile that reaches past the first query needs the mask,
        # or one that starts behind the last query's window
        edge = col0 + tk - 1 > row0
        if window is not None:
            edge |= col0 <= row0 + tq - 1 - window

        @pl.when(visited & edge)
        def _():
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            keep = rows >= cols
            if window is not None:
                keep &= rows - cols < window
            step(keep)

        pl.when(visited & jnp.logical_not(edge))(lambda: step(None))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        # the division after the second product, as the oracle has it
        for g in range(group):
            total = jnp.tile(l_ref[g], (1, dv // LANES))
            o_ref[g] = (acc_ref[g] / total).astype(o_ref.dtype)


def _attend_block(q, k, v, keep, out, *, lo: int, hi: int, tq: int, tk: int,
                  scale: float, interpret: bool,
                  window: Optional[int] = None):
    """Queries ``[lo, hi)`` of ``q`` [H, S, Dk] over keys ``[0, hi)`` of
    ``k`` / ``v`` [H_kv, S, D] (under a ``window`` over the last
    ``window`` of them a query), written into rows ``[lo, hi)`` of
    ``out`` [H, S, Dv] in place; ``out`` None makes the array. ``keep``
    int8 ``[nq*tq, nk*tk]`` or None for a causal block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, rows, dk = q.shape
    dv = v.shape[2]
    shared = heads // k.shape[0]        # query heads a key/value head
    # the heads of a grid step lie inside one key/value head's group,
    # or each has its own
    group = max(g for g in range(1, HEADS_PER_STEP + 1) if heads % g == 0
                and (shared == 1 or shared % g == 0))
    kv_group = group if shared == 1 else 1
    nq, nk, first = -(-(hi - lo) // tq), -(-hi // tk), lo // tq
    if window is not None:
        # key tiles a query tile walks, from its window's first to its
        # diagonal: the grid holds the longest walk and no step before it
        nk = max((lo + (i + 1) * tq - 1) // tk
                 - _first_tile(lo + i * tq, window, tk) + 1
                 for i in range(nq))

    def query_tile(h, i, j):
        return h, first + i, 0

    def key_tile(h, i, j):
        # an unvisited step names the tile it already holds: no copy
        if window is not None:
            j = j + _first_tile(lo + i * tq, window, tk)
        return (h if shared == 1 else h * group // shared,
                jnp.minimum(j, (lo + (i + 1) * tq - 1) // tk), 0)

    in_specs = [pl.BlockSpec((group, tq, dk), query_tile),
                pl.BlockSpec((kv_group, tk, dk), key_tile),
                pl.BlockSpec((kv_group, tk, dv), key_tile)]
    operands = [q, k, v]
    if keep is not None:
        in_specs.append(pl.BlockSpec(
            (tq, tk), lambda h, i, j: (i, key_tile(h, i, j)[1])))
        operands.append(keep)
    aliases = {}
    if out is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {len(operands): 0}
        operands.append(out)
    return pl.pallas_call(
        functools.partial(_attention_kernel, scale=scale, lo=lo, tq=tq,
                          tk=tk, masked=keep is not None, window=window),
        out_shape=jax.ShapeDtypeStruct((heads, rows, dv), v.dtype),
        grid=(heads // group, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((group, tq, dv), query_tile),
        scratch_shapes=[pltpu.VMEM((group, tq, LANES), jnp.float32),
                        pltpu.VMEM((group, tq, LANES), jnp.float32),
                        pltpu.VMEM((group, tq, dv), jnp.float32)],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="nns_masked_attention",
    )(*operands)


def blocked_causal_attention(
        q, k, v, *, scale: float, block_q: int,
        key_mask: Optional[Callable[[int, int], Optional[jax.Array]]] = None,
        window: Optional[int] = None, scope: Optional[str] = None):
    """``q`` [S, H, Dk], ``k`` [S, H_kv, Dk], ``v`` [S, H_kv, Dv] ->
    [S, H, Dv]; ``H_kv`` divides ``H`` and query head ``h`` reads
    key/value head ``h // (H / H_kv)``.

    Query block ``[lo, hi)`` attends keys ``[0, hi)`` with ``s <= t``,
    and under a ``window`` with ``t - window < s``;
    ``key_mask(lo, hi)`` returns bool ``[hi - lo, hi]`` (True = keep) or
    None for a block it leaves causal; every query must keep at least
    one key. Scores and softmax statistics in float32, the weights go to
    the second product in ``v``'s dtype, both products accumulate in
    float32. ``scope`` names this function's own operations in a trace
    (``jax.named_scope``); the mask's are named by whoever computes it.

    One ``nns_masked_attention`` call a block (module docstring), on
    head-major ``[H, S, D]`` operands: a caller that has them so and
    hands over their transposes pays for no transpose (XLA folds the
    pair), any other pays three and one. The query tile is the largest
    divisor of ``block_q`` that is a multiple of 8 and at most
    ``TILE_Q``; S is padded to the tiles and the head sizes to the lane
    width. A ``block_q`` with no such divisor raises, as Mosaic does
    for a tile it cannot lay out."""
    s = q.shape[0]
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} key "
                         f"and {v.shape[1]} value heads")
    if window is not None and window < 1:
        raise ValueError(f"window={window} keeps no key")
    tq = max((t for t in range(8, min(block_q, TILE_Q) + 1, 8)
              if block_q % t == 0), default=0)
    if not tq:
        raise ValueError(f"block_q={block_q} has no divisor that is a "
                         f"multiple of 8 and at most {TILE_Q}")
    tk = min(TILE_K, _round_up(s, LANES))
    rows = _round_up(s, math.lcm(tq, tk))
    interpret = jax.default_backend() != "tpu"

    def named():
        return jax.named_scope(scope) if scope else contextlib.nullcontext()

    def head_major(x):
        d = x.shape[2]
        return jnp.pad(jnp.transpose(x, (1, 0, 2)),
                       ((0, 0), (0, rows - s), (0, _round_up(d, LANES) - d)))

    with named():
        qh, kh, vh = head_major(q), head_major(k), head_major(v)
    out = None
    for lo in range(0, s, block_q):
        hi = min(lo + block_q, s)
        keep = key_mask(lo, hi) if key_mask is not None else None
        with named():
            if keep is not None:
                # the selection and the causal rule as one tile set
                keep &= jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None]
                if window is not None:
                    keep &= jnp.arange(lo, hi)[:, None] \
                        - jnp.arange(hi)[None] < window
                keep = jnp.pad(keep.astype(jnp.int8), (
                    (0, _round_up(hi - lo, tq) - (hi - lo)),
                    (0, _round_up(hi, tk) - hi)))
            out = _attend_block(qh, kh, vh, keep, out, lo=lo, hi=hi, tq=tq,
                                tk=tk, scale=scale, interpret=interpret,
                                window=window)
    with named():
        return jnp.transpose(out, (1, 0, 2))[:s, :, :v.shape[2]]
