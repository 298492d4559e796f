"""uint8 -> scaled float Pallas kernel (the worked custom-kernel example).

Every vision pipeline runs ``(x - offset) * scale`` (typically
``x/127.5 - 1``) on each frame right after H2D; this implements it as a
VMEM-tiled Pallas kernel with a jnp oracle for parity.

Honest framing (the pallas guide's own rule: don't hand-schedule what
XLA already fuses): for THIS op, XLA's fusion into the consuming matmul
is at least as good — the zoo models fold the affine into the jitted
graph and need no kernel. ops/ exists as the extension point for ops
XLA handles poorly (custom quant codecs, windowed sparse packing), and
this file is the template: kernel + oracle + interpret-mode tests +
on-device parity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# tile: 256 sublanes x 1024 lanes = 256 K elements per step (0.25 MB u8
# + 0.5 MB bf16) — small against the ~16 MB VMEM budget, wide enough to
# keep the VPU lanes full
_TILE_ROWS = 256
_LANES = 1024


def normalize_reference(x, scale: float, offset: float,
                        dtype=jnp.bfloat16):
    """The jnp oracle: (x - offset) * scale, cast to ``dtype``."""
    return ((x.astype(jnp.float32) - offset) * scale).astype(dtype)


def _kernel(scale: float, offset: float, out_dtype, x_ref, o_ref):
    # Mosaic has no direct u8->f32 cast; widen through int32 on the VPU
    x = x_ref[...].astype(jnp.int32).astype(jnp.float32)
    o_ref[...] = ((x - offset) * scale).astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "offset", "dtype", "interpret"))
def _normalize_pallas(x2d, scale: float, offset: float, dtype,
                      interpret: bool = False):
    from jax.experimental import pallas as pl

    rows = x2d.shape[0]
    tile = min(_TILE_ROWS, rows)
    grid = (rows + tile - 1) // tile
    # scope and kernel name: what a profiler trace calls this kernel
    with jax.named_scope("fused_normalize"):
        return pl.pallas_call(
            functools.partial(_kernel, scale, offset, dtype),
            grid=(grid,),
            in_specs=[pl.BlockSpec((tile, x2d.shape[1]),
                                   lambda i: (i, 0))],
            out_specs=pl.BlockSpec((tile, x2d.shape[1]), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x2d.shape, dtype),
            interpret=interpret,
            name="nns_fused_normalize",
        )(x2d)


def fused_normalize(x, scale: float = 1.0 / 127.5, offset: float = 127.5,
                    dtype=jnp.bfloat16):
    """(x - offset) * scale as one fused on-chip pass.

    Accepts any rank; internally reshaped to 2D lane-aligned tiles when
    the element count allows, else padded. The SAME kernel body runs
    everywhere: compiled by Mosaic on TPU, through the Pallas
    interpreter elsewhere (how tests exercise it on the CPU mesh).
    :func:`normalize_reference` is the oracle, never a stand-in.
    """
    interpret = jax.devices()[0].platform != "tpu"
    n = x.size
    # widest lane count (multiple of 128) that divides the element count
    # exactly: no padding copies on the common frame shapes
    cols = 0
    for cand in (_LANES, 512, 256, 128):
        if n % cand == 0:
            cols = cand
            break
    flat = jnp.ravel(x)
    if cols == 0:
        cols = 128
        rows = (n + cols - 1) // cols
        flat = jnp.pad(flat, (0, rows * cols - n))
    rows = flat.size // cols
    out = _normalize_pallas(flat.reshape(rows, cols),
                            float(scale), float(offset), dtype,
                            interpret=interpret)
    out = jnp.ravel(out)
    if out.size != n:
        out = out[:n]
    return out.reshape(x.shape)
