"""Vision Transformer — the dense-MXU vision model of the zoo.

MobileNet's depthwise convolutions under-use the systolic array by
construction (feature_group_count slices the MXU); a ViT is dense
matmuls end to end, so it is the model where MFU on TPU approaches the
hardware ceiling. Fills the classification slot the reference serves
with heavyweight backbones via its vendor SDK subplugins (ref:
ext/nnstreamer/tensor_filter/tensor_filter_tensorflow_lite.cc model
zoo usage in tests); here it is a first-class zoo citizen:

    zoo://vit?size=224&patch=16&d_model=768&layers=12&heads=12

Same output contract as mobilenet_v2 (uint8 frame in, [classes] float32
logits out) so image_labeling decodes it unchanged.
"""
from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..tensors.info import TensorsInfo
from .zoo import jit_init, register_model


class EncoderBlock(nn.Module):
    d_model: int
    heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    # fused=True routes the attention core through the Pallas kernel
    # (ops/attention.py): scores stay in VMEM instead of round-tripping
    # HBM as a [B,H,S,S] tensor. Same math, same params, same output —
    # a compile-time toggle, not a different model.
    fused: bool = False

    @nn.compact
    def __call__(self, x):
        # the scopes name each device op's half of the block in a
        # profiler trace (they touch neither params nor the program)
        with jax.named_scope("block/attn"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            attn_kwargs = {}
            if self.fused:
                from ..ops.attention import fused_attention
                attn_kwargs["attention_fn"] = fused_attention
            h = nn.MultiHeadDotProductAttention(
                num_heads=self.heads, dtype=self.dtype, **attn_kwargs)(h, h)
            x = x + h
        with jax.named_scope("block/mlp"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = nn.Dense(self.d_model * self.mlp_ratio, dtype=self.dtype)(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, dtype=self.dtype)(h)
            return x + h


class ViT(nn.Module):
    patch: int = 16
    d_model: int = 768
    layers: int = 12
    heads: int = 12
    classes: int = 1000
    dtype: Any = jnp.bfloat16
    fused: bool = False

    @nn.compact
    def __call__(self, x):
        # patch embedding: one conv with stride=kernel=patch (a dense
        # [p*p*3, d] matmul per patch on the MXU)
        with jax.named_scope("patch_embed"):
            x = nn.Conv(self.d_model, (self.patch, self.patch),
                        strides=(self.patch, self.patch), padding="VALID",
                        dtype=self.dtype)(x)
            b, hp, wp, d = x.shape
            x = x.reshape(b, hp * wp, d)
            pos = self.param("pos_embed", nn.initializers.normal(0.02),
                             (1, hp * wp, d), jnp.float32)
            x = x + pos.astype(self.dtype)
        for _ in range(self.layers):
            x = EncoderBlock(self.d_model, self.heads,
                             dtype=self.dtype, fused=self.fused)(x)
        with jax.named_scope("head"):
            x = nn.LayerNorm(dtype=self.dtype)(x)
            x = x.mean(axis=1)  # mean-pool (no cls token: shape-stable)
            return nn.Dense(self.classes, dtype=jnp.float32)(
                x.astype(jnp.float32))


@register_model("vit")
def _build_vit(size: str = "224", patch: str = "16", d_model: str = "768",
               layers: str = "12", heads: str = "12",
               classes: str = "1000", seed: str = "0",
               attn: str = "auto"):
    """``attn``: ``stock`` (flax/XLA attention), ``pallas`` (the fused
    VMEM kernel, ops/attention.py). The param tree is identical either
    way — the toggle changes only how the attention core is scheduled.
    ``auto`` resolves to stock; which is faster on the chip is not
    measured (ROADMAP Design 7)."""
    hw = int(size)
    if attn == "auto":
        attn = "stock"
    if attn not in ("stock", "pallas"):
        # a typo must not silently benchmark the wrong attention path
        raise ValueError(f"vit: attn must be auto|stock|pallas, "
                         f"got {attn!r}")
    model = ViT(patch=int(patch), d_model=int(d_model), layers=int(layers),
                heads=int(heads), classes=int(classes),
                fused=(attn == "pallas"))
    dummy = jnp.zeros((1, hw, hw, 3), jnp.bfloat16)
    params = jit_init(model, seed, dummy)

    def apply_fn(p, frame):
        batched = frame.ndim == 4
        x = frame.astype(jnp.bfloat16) / 127.5 - 1.0
        out = model.apply(p, x if batched else x[None])
        return out if batched else out[0]

    in_info = TensorsInfo.make("uint8", f"3:{hw}:{hw}")
    out_info = TensorsInfo.make("float32", classes)
    return apply_fn, params, in_info, out_info
