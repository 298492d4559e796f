"""Fused multi-head attention — Pallas TPU kernel.

The stock attention path materializes the [B, H, S, S] score tensor in
HBM twice (write after QK^T, read for softmax·V); for ViT-B/16 at
batch 64 that is ~1.2 GB of HBM traffic per layer that never needed to
leave the chip. This kernel keeps one (batch, head)'s whole score block
in VMEM: QK^T, masked f32 softmax and PV run back to back on the
MXU/VPU with only Q/K/V in and O out touching HBM (SURVEY.md §7 Pallas
stance: hand-fuse only what XLA cannot).

Scope: non-causal full-sequence attention with sequence lengths that
fit VMEM after padding to the 128-lane tile (S_pad^2 f32 scores; fine
through S≈1024 — the ViT/encoder regime). Causal attention of long
sequences, with or without a per-query key selection, is
``ops/sparse_attention.py``'s ``nns_masked_attention`` (key tiles walked
with a running softmax, no S bound; the lm block of models/glm_dsa.py
runs it); the two share no logic. Sequences split over chips belong to
the ring/Ulysses paths (parallel/ring.py), cached decode to the
KV-cache decode loop (models/transformer.py), not here.

Drop-in: :func:`fused_attention` matches the flax
``MultiHeadDotProductAttention(attention_fn=...)`` contract
([B, S, H, D] inputs, softmax over keys), so models opt in per-module
(models/vit.py ``attn=pallas``). The same kernel body runs everywhere:
compiled by Mosaic on TPU, through the Pallas interpreter on other
backends (how the CPU tests exercise it). :func:`reference_attention`
is the oracle, never a stand-in.

No reference analog: the reference's backends hand attention to vendor
SDKs; on TPU the fusion boundary is ours to place.

Status: opt-in (``zoo://vit?attn=pallas``), validated for parity;
``attn=auto`` resolves to stock XLA attention. Whether the kernel beats
XLA's own attention fusion at any shape on a chip local to the process
is not measured (ROADMAP Design 7).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def reference_attention(q, k, v):
    """jnp reference (the parity oracle): f32 softmax, same contract."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    p = jax.nn.softmax(s * (d ** -0.5), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, seq_len: int,
                 scale: float):
    # one (batch, head) per grid step: scores never leave VMEM
    q = q_ref[0]                      # [S_pad, D]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [S_pad, S_pad]
    if seq_len < s.shape[-1]:
        # padded key columns must not receive probability mass
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < seq_len, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o_ref[0] = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_bshd(q, k, v, interpret: bool = False):
    from jax.experimental import pallas as pl

    b, s_len, h, d = q.shape
    s_pad = _round_up(s_len, 128)
    d_pad = _round_up(d, 128)
    scale = d ** -0.5

    def prep(x):
        # [B,S,H,D] -> [B*H, S_pad, D_pad]: grid over fused batch*heads
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s_len, d)
        return jnp.pad(x, ((0, 0), (0, s_pad - s_len), (0, d_pad - d)))

    qp, kp, vp = prep(q), prep(k), prep(v)
    spec = pl.BlockSpec((1, s_pad, d_pad), lambda i: (i, 0, 0))
    # scope and kernel name: what a profiler trace calls this kernel
    with jax.named_scope("fused_attention"):
        out = pl.pallas_call(
            functools.partial(_attn_kernel, seq_len=s_len, scale=scale),
            out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d_pad), q.dtype),
            grid=(b * h,),
            in_specs=[spec, spec, spec],
            out_specs=spec,
            interpret=interpret,
            name="nns_fused_attention",
        )(qp, kp, vp)
    out = out[:, :s_len, :d].reshape(b, h, s_len, d)
    return jnp.transpose(out, (0, 2, 1, 3))


def fused_attention(query, key, value, bias=None, mask=None,
                    **unused_kwargs: Any):
    """flax ``attention_fn``-compatible fused attention.

    query/key/value: [B, S, H, D]. bias/mask are outside the kernel's
    contract (the encoder models this serves are full-attention);
    passing one runs stock flax attention, so a mask is never ignored.
    """
    if bias is not None or mask is not None:
        import flax.linen as nn
        return nn.dot_product_attention(query, key, value, bias=bias,
                                        mask=mask)
    return _fused_bshd(query, key, value,
                       interpret=jax.devices()[0].platform != "tpu")
