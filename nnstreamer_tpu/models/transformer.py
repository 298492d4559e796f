"""Decoder-only transformer LM — the flagship distributed/generative model.

Fills the slot of the reference's llama.cpp / llama2.c / executorch-llama
backends (ref: ext/nnstreamer/tensor_filter/tensor_filter_llamacpp.cc —
async token streaming; _llama2.cc), but built TPU-first:

* plain-JAX param pytree with stable names so mesh partition rules are
  regex-over-path (see parallel/sharding.py) — Megatron-style tensor
  parallelism (column-split wq/wk/wv/w1/w3, row-split wo/w2);
* RoPE positions, RMSNorm, SwiGLU MLP, causal attention — all static
  shapes, scan-friendly;
* sequence parallelism via ring attention (parallel/ring.py) when a
  ``seq`` mesh axis is present;
* KV-cache single-token decode step for the generative filter path.

Zoo entries: ``zoo://gpt?...`` (logits fn) used by tests/bench; the
generative pipeline uses filters/llm.py on top of this module.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..tensors.info import TensorsInfo
from .zoo import register_model


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 0          # 0 -> 4*d_model
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # distributed knobs (None = single chip)
    mesh: Optional[jax.sharding.Mesh] = None
    data_axis: Optional[str] = "data"
    seq_axis: Optional[str] = None     # set to e.g. "seq" for seq parallelism
    model_axis: Optional[str] = "model"
    # sequence-parallel attention scheme: "ring" (K/V ppermute ring,
    # any head count) or "ulysses" (all-to-all head/seq exchange, needs
    # heads % seq_axis_size == 0; fewer collectives) — both exact
    seq_scheme: str = "ring"

    @property
    def ff(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def init_params(cfg: GPTConfig, key: jax.Array) -> Dict[str, Any]:
    """Param tree with path names the partition rules key off.

    Jitted on ``cfg`` (frozen, hashable): the whole tree materializes in
    ONE compiled dispatch instead of 9x n_layers eager ops."""
    return _init_params_jit(cfg, key)


@partial(jax.jit, static_argnums=0)
def _init_params_jit(cfg: GPTConfig, key: jax.Array) -> Dict[str, Any]:
    dt = cfg.dtype
    d, f, v = cfg.d_model, cfg.ff, cfg.vocab

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    keys = jax.random.split(key, 2 + cfg.n_layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (v, d), d ** -0.5),
        "head": dense(keys[1], (d, v), d ** -0.5),
        "ln_f": jnp.ones((d,), dt),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        ks = jax.random.split(keys[2 + i], 7)
        params["layers"].append({
            "ln1": jnp.ones((d,), dt),
            "wq": dense(ks[0], (d, d), d ** -0.5),
            "wk": dense(ks[1], (d, d), d ** -0.5),
            "wv": dense(ks[2], (d, d), d ** -0.5),
            "wo": dense(ks[3], (d, d), (2 * d * cfg.n_layers) ** -0.5),
            "ln2": jnp.ones((d,), dt),
            "w1": dense(ks[4], (d, f), d ** -0.5),
            "w3": dense(ks[5], (d, f), d ** -0.5),
            "w2": dense(ks[6], (f, d), (2 * f * cfg.n_layers) ** -0.5),
        })
    return params


def rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope(x, positions, theta: float):
    """Rotary embedding over the last dim. x: [..., S, H, Dh]."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, Dh/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _constrain(x, cfg: GPTConfig, spec: Tuple):
    """Activation sharding hint; no-op off-mesh."""
    if cfg.mesh is None:
        return x
    axes = tuple(a if (a is None or a in cfg.mesh.axis_names) else None
                 for a in spec)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(cfg.mesh, jax.sharding.PartitionSpec(*axes)))


def _dense_attention(q, k, v, positions_q, positions_k):
    """q,k,v: [B,S,H,Dh]; causal by absolute position."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = positions_q[:, None, :, None] >= positions_k[:, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attention(q, k, v, positions, cfg: GPTConfig):
    if cfg.seq_scheme not in ("ring", "ulysses"):
        # both schemes are exact, so a typo would be undetectable from
        # outputs — fail loudly instead of silently running ring
        raise ValueError(f"unknown seq_scheme {cfg.seq_scheme!r}; "
                         "expected 'ring' or 'ulysses'")
    if cfg.mesh is not None and cfg.seq_axis in cfg.mesh.axis_names \
            and cfg.mesh.shape[cfg.seq_axis] > 1:
        if cfg.seq_scheme == "ulysses":
            from ..parallel.ulysses import ulysses_attention_sharded
            return ulysses_attention_sharded(
                q, k, v, cfg.mesh, cfg.data_axis, cfg.seq_axis,
                cfg.model_axis)
        from ..parallel.ring import ring_attention_sharded
        return ring_attention_sharded(q, k, v, cfg.mesh, cfg.data_axis,
                                      cfg.seq_axis, cfg.model_axis)
    return _dense_attention(q, k, v, positions, positions)


def block(h, layer, positions, cfg: GPTConfig, return_kv: bool = False):
    """One transformer block; with ``return_kv`` also hands back the
    roped K and raw V so prefill can seed a decode cache from the SAME
    computation (no duplicated block body)."""
    b, s, d = h.shape
    hd, nh = cfg.head_dim, cfg.n_heads
    with jax.named_scope("block/attn"):
        x = rmsnorm(h, layer["ln1"])
        q = (x @ layer["wq"]).reshape(b, s, nh, hd)
        k = (x @ layer["wk"]).reshape(b, s, nh, hd)
        v = (x @ layer["wv"]).reshape(b, s, nh, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        attn = _attention(q, k, v, positions, cfg)
        h = h + attn.reshape(b, s, d) @ layer["wo"]
        h = _constrain(h, cfg, (cfg.data_axis, cfg.seq_axis, None))
    with jax.named_scope("block/mlp"):
        x = rmsnorm(h, layer["ln2"])
        ff = jax.nn.silu(x @ layer["w1"]) * (x @ layer["w3"])
        ff = _constrain(ff, cfg, (cfg.data_axis, cfg.seq_axis, cfg.model_axis))
        h = h + ff @ layer["w2"]
        h = _constrain(h, cfg, (cfg.data_axis, cfg.seq_axis, None))
    return (h, k, v) if return_kv else h


def forward(params, tokens, cfg: GPTConfig):
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
        h = _constrain(h, cfg, (cfg.data_axis, cfg.seq_axis, None))
    for layer in params["layers"]:
        h = block(h, layer, positions, cfg)
    with jax.named_scope("lm_head"):
        h = rmsnorm(h, params["ln_f"])
        logits = (h @ params["head"]).astype(jnp.float32)
    return _constrain(logits, cfg, (cfg.data_axis, cfg.seq_axis, cfg.model_axis))


def loss_fn(params, batch, cfg: GPTConfig):
    """Next-token cross-entropy; batch = tokens [B,S+1] int32."""
    inputs, targets = batch[:, :-1], batch[:, 1:]
    logits = forward(params, inputs, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# -- KV-cache decode (generative path) ------------------------------------

def init_cache(cfg: GPTConfig, batch: int, max_len: int) -> Dict[str, Any]:
    shape = (cfg.n_layers, batch, max_len, cfg.n_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "index": jnp.zeros((), jnp.int32)}


def prefill(params, cache, tokens, cfg: GPTConfig, true_len=None):
    """Whole-prompt prefill in ONE dispatch: tokens [B,T] int32 ->
    (logits [B,V] for the last real position, cache with K/V written at
    positions 0..T-1 and index=true_len).

    ≙ llamacpp's n_batch prompt ingestion
    (tensor_filter_llamacpp.cc:267) — the causal forward runs batched on
    the MXU instead of T sequential single-token dispatches; the decode
    loop then continues from the returned cache. Built on the same
    block() as forward(), so mesh sharding constraints and ring
    attention apply to prefill too.

    ``true_len`` (a traced int32 scalar <= T) supports length-bucketed
    padding: callers pad prompts to a few fixed shapes so jit compiles
    O(log max_len) variants instead of one per prompt length. Padded
    positions are causal-masked garbage that is never read: logits come
    from position true_len-1, and the decode loop overwrites padded
    cache slots (at positions >= true_len) before its validity mask
    (arange <= pos) can reach them.
    """
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
        h = _constrain(h, cfg, (cfg.data_axis, cfg.seq_axis, None))
    new_k, new_v = [], []
    for i, layer in enumerate(params["layers"]):
        h, k, v = block(h, layer, positions, cfg, return_kv=True)
        new_k.append(jax.lax.dynamic_update_slice(
            cache["k"][i], k.astype(cache["k"].dtype), (0, 0, 0, 0)))
        new_v.append(jax.lax.dynamic_update_slice(
            cache["v"][i], v.astype(cache["v"].dtype), (0, 0, 0, 0)))
    with jax.named_scope("lm_head"):
        h = rmsnorm(h, params["ln_f"])
        t_eff = jnp.asarray(t if true_len is None else true_len, jnp.int32)
        # dynamic index on the seq axis; clamps (never wraps) when out of
        # range, so a zero-length prompt cannot read the padded tail
        h_last = jax.lax.dynamic_slice_in_dim(h, t_eff - 1, 1, axis=1)[:, 0]
        logits = (h_last @ params["head"]).astype(jnp.float32)
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v),
             "index": t_eff}
    return logits, cache


def decode_step(params, cache, token, cfg: GPTConfig):
    """One-token decode: token [B] int32 -> (logits [B,V], new cache).

    The cache is functional state threaded by the caller — the XLA-friendly
    shape of llamacpp's internal context (static shapes, dynamic_update_slice).
    A thin shim over :func:`decode_step_multi` (shared scalar index
    broadcast to per-row positions) so the single- and multi-stream paths
    cannot drift."""
    b = token.shape[0]
    mcache = {"k": cache["k"], "v": cache["v"],
              "index": jnp.broadcast_to(cache["index"], (b,))}
    logits, mcache = decode_step_multi(
        params, mcache, token, jnp.ones((b,), bool), cfg)
    return logits, {"k": mcache["k"], "v": mcache["v"],
                    "index": mcache["index"][0]}


def init_cache_multi(cfg: GPTConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Continuous-batching cache: per-slot positions (index [B]) so B
    independent streams at different depths share one decode dispatch."""
    cache = init_cache(cfg, batch, max_len)
    cache["index"] = jnp.zeros((batch,), jnp.int32)
    return cache


def cache_insert(bcache, cache1, slot):
    """Insert a batch-1 prefill cache into slot ``slot`` of a
    multi-stream cache (same max_len). The whole K/V slice is replaced,
    so stale tokens from the slot's previous occupant cannot leak."""
    k = jax.lax.dynamic_update_slice(
        bcache["k"], cache1["k"].astype(bcache["k"].dtype), (0, slot, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(
        bcache["v"], cache1["v"].astype(bcache["v"].dtype), (0, slot, 0, 0, 0))
    idx = jax.lax.dynamic_update_slice(
        bcache["index"], cache1["index"].reshape(1).astype(jnp.int32), (slot,))
    return {"k": k, "v": v, "index": idx}


def decode_step_multi(params, cache, token, active, cfg: GPTConfig):
    """One decode step for B *independent* streams in ONE dispatch
    (continuous-batching lite — the TPU-first answer to llamacpp's
    n_batch, tensor_filter_llamacpp.cc:267). token [B] int32,
    active [B] bool; cache index is per-slot [B]. Inactive slots do not
    advance their index; their lanes compute garbage that the scheduler
    never emits. Lanes whose position has reached max_len likewise
    neither write nor advance: dynamic_update_slice would clamp such a
    write onto row max_len-1, corrupting the last real cache row — the
    in-graph form of the single-stream loop's "never decode past
    capacity" guard (the emitted token stream is unchanged: logits a
    full lane produces past capacity are never sampled)."""
    b = token.shape[0]
    pos = cache["index"]                       # [B]
    positions = pos[:, None]                   # [B,1]
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], token[:, None], axis=0)
    max_len = cache["k"].shape[2]
    valid = jnp.arange(max_len)[None, :] <= pos[:, None]   # [B,L]
    ok = active & (pos < max_len)              # may write + advance
    lane = ok[:, None, None, None]             # [B,1,1,1] over [B,1,nh,hd]
    # per-slot cache write: each row lands at its own position. Guarded
    # lanes write their OLD row back (a no-op) instead of their new k/v:
    # masking the one-row update is free, where a whole-cache select
    # per layer would double the decode step's HBM traffic
    upd = jax.vmap(
        lambda c, x, p: jax.lax.dynamic_update_slice(c, x, (p, 0, 0)))
    row = jax.vmap(
        lambda c, p: jax.lax.dynamic_slice(
            c, (p, 0, 0), (1, c.shape[1], c.shape[2])))
    new_k, new_v = [], []
    for i, layer in enumerate(params["layers"]):
        hd, nh = cfg.head_dim, cfg.n_heads
        with jax.named_scope("block/attn"):
            x = rmsnorm(h, layer["ln1"])
            q = rope((x @ layer["wq"]).reshape(b, 1, nh, hd), positions,
                     cfg.rope_theta)
            k1 = rope((x @ layer["wk"]).reshape(b, 1, nh, hd), positions,
                      cfg.rope_theta)
            v1 = (x @ layer["wv"]).reshape(b, 1, nh, hd)
            k = upd(cache["k"][i],
                    jnp.where(lane, k1.astype(cache["k"].dtype),
                              row(cache["k"][i], pos)), pos)
            v = upd(cache["v"][i],
                    jnp.where(lane, v1.astype(cache["v"].dtype),
                              row(cache["v"][i], pos)), pos)
            new_k.append(k)
            new_v.append(v)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            scores = scores * (hd ** -0.5)
            scores = jnp.where(valid[:, None, None, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            h = h + attn.reshape(b, 1, -1) @ layer["wo"]
        with jax.named_scope("block/mlp"):
            x = rmsnorm(h, layer["ln2"])
            ff = jax.nn.silu(x @ layer["w1"]) * (x @ layer["w3"])
            h = h + ff @ layer["w2"]
    with jax.named_scope("lm_head"):
        h = rmsnorm(h, params["ln_f"])
        logits = (h[:, 0] @ params["head"]).astype(jnp.float32)
    cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v),
             "index": pos + ok.astype(jnp.int32)}
    return logits, cache


def sample_logits(keys, logits, temperature: float, top_k: int = 0,
                  top_p: float = 1.0):
    """Per-stream token sampling, jit-safe, shared by the host decode
    loops and the scanned chunk body so every path draws identical
    tokens for the same keys.

    keys [B,2] uint32, logits [B,V] f32 -> [B] int32. temperature<=0 is
    greedy argmax (keys ignored). top_k keeps the K best logits, top_p
    the smallest prefix of the sorted distribution with cumulative
    probability >= p (nucleus sampling) — the knobs llamacpp exposes on
    the reference's generative slot (tensor_filter_llamacpp.cc sampler
    chain), computed in-graph on device.
    """
    if temperature <= 0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    # llamacpp chain order: the top_k/top_p nucleus is formed on the
    # UNSCALED distribution, temperature only shapes the final draw —
    # so migrated configs keep their candidate sets
    l0 = logits.astype(jnp.float32)
    if top_k and top_k > 0:
        kth = jax.lax.top_k(l0, min(top_k, l0.shape[-1]))[0][..., -1:]
        l0 = jnp.where(l0 < kth, -jnp.inf, l0)
    if top_p < 1.0:
        srt = jnp.flip(jnp.sort(l0, axis=-1), axis=-1)
        probs = jax.nn.softmax(srt, axis=-1)
        exclusive = jnp.cumsum(probs, axis=-1) - probs
        # exclusive <= 0 always keeps the best token: top_p<=0 must
        # degrade to greedy, not to an all-masked row (categorical over
        # all -inf silently returns index 0)
        kept = jnp.where((exclusive < top_p) | (exclusive <= 0.0),
                         srt, jnp.inf)
        thr = jnp.min(kept, axis=-1, keepdims=True)  # smallest kept logit
        l0 = jnp.where(l0 < thr, -jnp.inf, l0)
    return jax.vmap(lambda k, row: jax.random.categorical(k, row))(
        keys, l0 / temperature).astype(jnp.int32)


def decode_chunk_multi(params, cache, logits, keys, active, cfg: GPTConfig,
                       *, steps: int, temperature: float = 0.0,
                       top_k: int = 0, top_p: float = 1.0):
    """``steps`` sample+decode rounds for B streams in ONE dispatch.

    A ``lax.scan`` over :func:`decode_step_multi` with the sampling
    (greedy argmax, or categorical at ``temperature``) folded into the
    graph, so token generation costs 1/steps of the dispatches and
    1/steps of the host syncs: the caller fetches a [steps, B] token
    block instead of B ids per step. The per-stream key-split order matches the host-side
    sampling loop exactly, so chunked and unchunked generation emit
    identical tokens for the same seed.

    The reference's llamacpp slot has no analog (its decode loop is
    host-driven per token); this is the XLA-native shape of generation:
    static chunk length, in-graph control flow (SURVEY.md §7 stance).

    Args: logits [B,V] from prefill or the previous chunk; keys [B,2]
    uint32 PRNG keys (ignored when temperature==0); active [B] bool.
    Returns (tokens [steps, B] int32, logits, cache, keys).
    """
    def body(carry, _):
        lg, ca, ks = carry
        if temperature > 0:
            pair = jax.vmap(jax.random.split)(ks)      # [B,2,2]
            ks2, subs = pair[:, 0], pair[:, 1]
            tok = sample_logits(subs, lg, temperature, top_k, top_p)
        else:
            ks2 = ks
            tok = sample_logits(ks, lg, 0.0)
        lg2, ca2 = decode_step_multi(params, ca, tok, active, cfg)
        return (lg2, ca2, ks2), tok

    (logits, cache, keys), toks = jax.lax.scan(
        body, (logits, cache, keys), None, length=steps)
    return toks, logits, cache, keys


# -- paged KV pool (block-granular cache, vLLM-style) ---------------------
#
# The contiguous multi-stream cache above reserves a worst-case
# [max_len] lane per slot, so decode occupancy is stream-counted. The
# pool below is the token-budgeted alternative: a shared arena of
# fixed-size blocks ([L, NB, bs, H, Dh]) addressed through per-stream
# block tables, with allocation/refcounts/prefix-sharing managed
# host-side (filters/kvpool.py). decode_step_paged gathers a stream's
# blocks into the SAME [B, max_len] layout decode_step_multi attends
# over and runs the identical op sequence on it, so the paged path is
# bit-exact against the contiguous path on CPU — the parity gate
# tests/test_llm_disagg.py enforces.

def init_kv_pool(cfg: GPTConfig, n_blocks: int, block_size: int) -> Dict[str, Any]:
    """Block arena: {"k","v"} [L, NB, bs, H, Dh]. Block 0 is an
    ordinary block; the host allocator decides which phys ids are live.
    Index NB (one past the end) is the discard target for guarded
    scatter writes (mode="drop")."""
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def pool_insert(pool, kb, vb, phys):
    """Write whole blocks: kb/vb [L, nb, bs, H, Dh] into phys [nb].
    Entire blocks are replaced, so a reused block cannot leak its
    previous occupant's rows into the freshly inserted span."""
    return {"k": pool["k"].at[:, phys].set(kb.astype(pool["k"].dtype),
                                           mode="drop"),
            "v": pool["v"].at[:, phys].set(vb.astype(pool["v"].dtype),
                                           mode="drop")}


def pool_copy_block(pool, src, dst):
    """Copy-on-write helper: duplicate block ``src`` into ``dst`` so a
    writer can diverge from a shared prefix block without touching the
    readers' copy."""
    return {"k": pool["k"].at[:, dst].set(pool["k"][:, src]),
            "v": pool["v"].at[:, dst].set(pool["v"][:, src])}


def pool_gather(pool, phys):
    """Gather blocks phys [nb] -> contiguous (k, v) [L, nb*bs, H, Dh]
    (the shipped-KV / prefill-with-past layout)."""
    k = pool["k"][:, phys]
    v = pool["v"][:, phys]
    flat = (k.shape[0], k.shape[1] * k.shape[2], k.shape[3], k.shape[4])
    return k.reshape(flat), v.reshape(flat)


def decode_step_paged(params, pool, table, index, token, active,
                      cfg: GPTConfig, *, max_len: int):
    """One decode step for B streams whose KV lives in pool blocks.

    table [B, W] int32 maps each stream's block index to a phys block;
    index [B] is the per-stream position. Each layer gathers the
    stream's blocks into a contiguous [B, max_len] view and then runs
    decode_step_multi's exact op sequence on it (same one-row masked
    update, same einsums, same [B, max_len] mask shape), so logits are
    bit-identical to the contiguous path — gathered bytes equal lane
    bytes, and the trailing W*bs - max_len garbage columns are sliced
    off before the softmax ever sees them. The new row is persisted
    into the pool by a separate guarded scatter: inactive / at-capacity
    lanes aim at phys id NB (one past the arena) and mode="drop"
    discards the write, the scatter-shaped form of decode_step_multi's
    "guarded lanes rewrite their old row" trick.

    Returns (logits [B,V], pool', index'). Shared prefix blocks are
    never written: the host allocator caps prefix adoption below the
    first decode-written block, so every scatter target is
    stream-private by construction."""
    b = token.shape[0]
    nb, bs_blk = pool["k"].shape[1], pool["k"].shape[2]
    hd, nh = cfg.head_dim, cfg.n_heads
    pos = index                                # [B]
    positions = pos[:, None]
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], token[:, None], axis=0)
    valid = jnp.arange(max_len)[None, :] <= pos[:, None]
    ok = active & (pos < max_len)
    lane = ok[:, None, None, None]
    upd = jax.vmap(
        lambda c, x, p: jax.lax.dynamic_update_slice(c, x, (p, 0, 0)))
    row = jax.vmap(
        lambda c, p: jax.lax.dynamic_slice(
            c, (p, 0, 0), (1, c.shape[1], c.shape[2])))
    blk = jnp.clip(pos // bs_blk, 0, table.shape[1] - 1)
    phys = jnp.take_along_axis(table, blk[:, None], axis=1)[:, 0]
    tgt = jnp.where(ok, phys, nb)              # nb = discard target
    off = pos % bs_blk
    k_rows, v_rows = [], []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope("block/attn"):
            kc = pool["k"][i][table].reshape(b, -1, nh, hd)[:, :max_len]
            vc = pool["v"][i][table].reshape(b, -1, nh, hd)[:, :max_len]
            x = rmsnorm(h, layer["ln1"])
            q = rope((x @ layer["wq"]).reshape(b, 1, nh, hd), positions,
                     cfg.rope_theta)
            k1 = rope((x @ layer["wk"]).reshape(b, 1, nh, hd), positions,
                      cfg.rope_theta)
            v1 = (x @ layer["wv"]).reshape(b, 1, nh, hd)
            kd = jnp.where(lane, k1.astype(kc.dtype), row(kc, pos))
            vd = jnp.where(lane, v1.astype(vc.dtype), row(vc, pos))
            k = upd(kc, kd, pos)
            v = upd(vc, vd, pos)
            k_rows.append(kd[:, 0])
            v_rows.append(vd[:, 0])
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            scores = scores * (hd ** -0.5)
            scores = jnp.where(valid[:, None, None, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            h = h + attn.reshape(b, 1, -1) @ layer["wo"]
        with jax.named_scope("block/mlp"):
            x = rmsnorm(h, layer["ln2"])
            ff = jax.nn.silu(x @ layer["w1"]) * (x @ layer["w3"])
            h = h + ff @ layer["w2"]
    with jax.named_scope("lm_head"):
        h = rmsnorm(h, params["ln_f"])
        logits = (h[:, 0] @ params["head"]).astype(jnp.float32)
    pool = {"k": pool["k"].at[:, tgt, off].set(jnp.stack(k_rows),
                                               mode="drop"),
            "v": pool["v"].at[:, tgt, off].set(jnp.stack(v_rows),
                                               mode="drop")}
    return logits, pool, pos + ok.astype(jnp.int32)


def decode_chunk_paged(params, pool, table, index, logits, keys, active,
                       cfg: GPTConfig, *, steps: int, max_len: int,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0):
    """``steps`` sample+decode rounds over the paged cache in ONE
    dispatch — decode_chunk_multi's scan body with decode_step_paged
    substituted. The block table is a scan constant: the scheduler
    admits new streams only between chunks, and each stream's blocks
    are preallocated through its emit budget, so no table edit can be
    needed mid-chunk. The per-stream key-split order matches
    decode_chunk_multi exactly, so paged chunked generation emits the
    same tokens as every other path for the same seed.

    Returns (tokens [steps, B] int32, logits, pool, index, keys)."""
    def body(carry, _):
        lg, pl, idx, ks = carry
        if temperature > 0:
            pair = jax.vmap(jax.random.split)(ks)
            ks2, subs = pair[:, 0], pair[:, 1]
            tok = sample_logits(subs, lg, temperature, top_k, top_p)
        else:
            ks2 = ks
            tok = sample_logits(ks, lg, 0.0)
        lg2, pl2, idx2 = decode_step_paged(
            params, pl, table, idx, tok, active, cfg, max_len=max_len)
        return (lg2, pl2, idx2, ks2), tok

    (logits, pool, index, keys), toks = jax.lax.scan(
        body, (logits, pool, index, keys), None, length=steps)
    return toks, logits, pool, index, keys


def prefill_with_past(params, past_k, past_v, past_len, tokens,
                      cfg: GPTConfig, true_len=None):
    """Suffix prefill over an existing KV prefix: run the prompt TAIL
    (tokens [1, S], ``true_len`` real) with attention over
    concat(past, suffix), where past_k/past_v [L, P, H, Dh] hold
    ``past_len`` valid rows (the rest padded garbage, column-masked).

    This is the other half of the prefix cache and of the wire KV
    handoff: a prompt whose first ``past_len`` tokens hit warm blocks
    (or arrived from a prefill replica) only pays compute for the
    suffix. RoPE positions are offset by ``past_len`` (traced, so one
    compiled variant serves every split point of a (P, S) bucket pair)
    and causality is by absolute position, exactly as in block().

    Returns (logits [1, V] at suffix position true_len-1,
    suffix K [L, S, H, Dh], suffix V) — the caller block-aligns and
    inserts the suffix KV into the pool."""
    b, s = tokens.shape
    p = past_k.shape[1]
    hd, nh = cfg.head_dim, cfg.n_heads
    p0 = jnp.asarray(past_len, jnp.int32)
    pos_q = p0 + jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    past_cols = jnp.arange(p, dtype=jnp.int32)
    # padded past rows sit at absolute positions < pos_q, so the causal
    # mask alone would admit them — the column-validity mask is load-bearing
    col_ok = jnp.concatenate([past_cols < p0, jnp.ones((s,), bool)])
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
    new_k, new_v = [], []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope("block/attn"):
            x = rmsnorm(h, layer["ln1"])
            q = rope((x @ layer["wq"]).reshape(b, s, nh, hd), pos_q,
                     cfg.rope_theta)
            k = rope((x @ layer["wk"]).reshape(b, s, nh, hd), pos_q,
                     cfg.rope_theta)
            v = (x @ layer["wv"]).reshape(b, s, nh, hd)
            fk = jnp.concatenate(
                [jnp.broadcast_to(past_k[i][None].astype(k.dtype),
                                  (b, p, nh, hd)), k], axis=1)
            fv = jnp.concatenate(
                [jnp.broadcast_to(past_v[i][None].astype(v.dtype),
                                  (b, p, nh, hd)), v], axis=1)
            pos_k = jnp.concatenate(
                [jnp.broadcast_to(past_cols, (b, p)), pos_q], axis=1)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, fk).astype(jnp.float32)
            scores = scores * (hd ** -0.5)
            mask = (pos_q[:, None, :, None] >= pos_k[:, None, None, :]) \
                & col_ok[None, None, None, :]
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, fv)
            h = h + attn.reshape(b, s, -1) @ layer["wo"]
        with jax.named_scope("block/mlp"):
            x = rmsnorm(h, layer["ln2"])
            ff = jax.nn.silu(x @ layer["w1"]) * (x @ layer["w3"])
            h = h + ff @ layer["w2"]
        new_k.append(k)
        new_v.append(v)
    with jax.named_scope("lm_head"):
        h = rmsnorm(h, params["ln_f"])
        t_eff = jnp.asarray(s if true_len is None else true_len, jnp.int32)
        h_last = jax.lax.dynamic_slice_in_dim(h, t_eff - 1, 1, axis=1)[:, 0]
        logits = (h_last @ params["head"]).astype(jnp.float32)
    # single-stream path (b == 1): drop the batch dim so the suffix KV
    # has the same [L, S, H, Dh] layout as shipped / gathered KV
    return logits, jnp.stack(new_k)[:, 0], jnp.stack(new_v)[:, 0]


@register_model("gpt")
def _build_gpt(vocab: str = "32000", d_model: str = "512", n_heads: str = "8",
               n_layers: str = "6", seq: str = "128", seed: str = "0"):
    """Logit-model zoo entry: int32 token frame [S] -> float32 logits [S,V]."""
    cfg = GPTConfig(vocab=int(vocab), d_model=int(d_model),
                    n_heads=int(n_heads), n_layers=int(n_layers))
    params = init_params(cfg, jax.random.PRNGKey(int(seed)))
    s = int(seq)

    def apply_fn(p, tokens):
        return forward(p, tokens[None].astype(jnp.int32), cfg)[0]

    in_info = TensorsInfo.make("int32", str(s))
    out_info = TensorsInfo.make("float32", f"{cfg.vocab}:{s}")
    return apply_fn, params, in_info, out_info
