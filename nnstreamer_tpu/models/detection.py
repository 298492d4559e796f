"""Detection / pose / segmentation zoo models (flax, MXU-first).

The BASELINE configs 2-4 (SSD-MobileNet-v2 bounding boxes, PoseNet
multi-output, DeepLab-v3 segmentation — BASELINE.md table) need native
models wired to the existing decoders:

- ``zoo://ssd_mobilenet_v2``   -> bounding_boxes mode=mobilenet-ssd-postprocess
  (emits the TFLite detection-postprocess tensor quad: boxes [N,4]
  ymin:xmin:ymax:xmax normalized, classes [N], scores [N], count [1] —
  ≙ ext/nnstreamer/tensor_decoder/box_properties/mobilenetssdpp.cc)
- ``zoo://posenet``            -> pose_estimation (heatmaps [H',W',K]
  ≙ tensordec-pose.c heatmap mode)
- ``zoo://deeplab_v3``         -> image_segment (logits [H,W,21]
  ≙ tensordec-imagesegment.c tflite-deeplab mode)

All share the MobileNetV2 backbone (models/mobilenet.py), run conv math
in bfloat16 on the MXU, and keep their postprocessing INSIDE the jitted
graph (top-k on device, resize on device) so one invoke = one XLA
program. Random init by default; ``params_dir=`` loads trained weights.
"""
from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..tensors.info import TensorsInfo
from .mobilenet import ConvBN, MobileNetV2, _V2_BLOCKS, _make_divisible
from .zoo import jit_init, register_model


class _Backbone(nn.Module):
    """MobileNetV2 feature extractor up to a chosen stride (8/16/32)."""

    width: float = 1.0
    max_stride: int = 16
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        stride = 2
        x = ConvBN(_make_divisible(32 * self.width), kernel=(3, 3),
                   strides=(2, 2), dtype=self.dtype)(x)
        from .mobilenet import InvertedResidual
        for t, c, n, s in _V2_BLOCKS:
            ch = _make_divisible(c * self.width)
            for i in range(n):
                blk_s = s if i == 0 else 1
                if stride * blk_s > self.max_stride:
                    blk_s = 1  # atrous-style: keep resolution
                stride *= blk_s if i == 0 and s > 1 and \
                    stride * s <= self.max_stride else 1
                x = InvertedResidual(ch, (blk_s, blk_s), t,
                                     dtype=self.dtype)(x)
        return x


class SSDHead(nn.Module):
    """Single-scale dense detection head (anchor-free center style):
    per-cell class scores + box offsets, postprocessed to the ssd-pp
    tensor quad in-graph."""

    num_classes: int = 91
    topk: int = 100
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, feat):
        h, w, _ = feat.shape[-3:]
        cls = nn.Conv(self.num_classes, (3, 3), padding="SAME",
                      dtype=self.dtype)(feat)
        box = nn.Conv(4, (3, 3), padding="SAME", dtype=self.dtype)(feat)
        scores = jax.nn.sigmoid(cls.astype(jnp.float32)).reshape(
            -1, self.num_classes)
        deltas = jnp.tanh(box.astype(jnp.float32)).reshape(-1, 4)
        # anchor grid: one center anchor per cell
        ys, xs = jnp.meshgrid(
            (jnp.arange(h) + 0.5) / h, (jnp.arange(w) + 0.5) / w,
            indexing="ij")
        cy = ys.reshape(-1) + deltas[:, 0] * 0.5
        cx = xs.reshape(-1) + deltas[:, 1] * 0.5
        bh = jnp.exp(deltas[:, 2]) * (2.0 / h)
        bw = jnp.exp(deltas[:, 3]) * (2.0 / w)
        best = jnp.max(scores, axis=1)
        cls_id = jnp.argmax(scores, axis=1)
        top_scores, idx = jax.lax.top_k(best, self.topk)
        boxes = jnp.stack([
            jnp.clip(cy[idx] - bh[idx] / 2, 0, 1),
            jnp.clip(cx[idx] - bw[idx] / 2, 0, 1),
            jnp.clip(cy[idx] + bh[idx] / 2, 0, 1),
            jnp.clip(cx[idx] + bw[idx] / 2, 0, 1)], axis=1)
        return (boxes, cls_id[idx].astype(jnp.float32), top_scores,
                jnp.asarray([float(self.topk)], jnp.float32))


class SSDMobileNetV2(nn.Module):
    num_classes: int = 91
    width: float = 1.0
    topk: int = 100

    @nn.compact
    def __call__(self, x):
        feat = _Backbone(width=self.width, max_stride=16)(x)
        return SSDHead(num_classes=self.num_classes, topk=self.topk)(feat)


@register_model("ssd_mobilenet_v2")
def _build_ssd(width: str = "1.0", num_classes: str = "91",
               size: str = "300", topk: str = "100", seed: str = "0",
               packed: str = "0"):
    """``packed=1`` concatenates the ssd-pp quad into ONE flat float32
    tensor [6K+1] inside the jitted graph (free on device), so a host
    consumer pays a single D2H instead of four. The bounding_boxes
    decoder unpacks the layout transparently."""
    w, nc, hw, k = float(width), int(num_classes), int(size), int(topk)
    want_packed = packed not in ("0", "", "false")
    model = SSDMobileNetV2(num_classes=nc, width=w, topk=k)
    dummy = jnp.zeros((1, hw, hw, 3), jnp.bfloat16)
    params = jit_init(model, seed, dummy)

    def apply_one(p, frame):
        x = frame.astype(jnp.bfloat16) / 127.5 - 1.0
        boxes, classes, scores, count = model.apply(p, x[None])
        if want_packed:
            return jnp.concatenate([boxes.reshape(-1), classes,
                                    scores, count])
        return boxes, classes, scores, count

    def apply_fn(p, frame):
        if frame.ndim == 4:  # batched invoke: vmap the per-frame path
            return jax.vmap(lambda f: apply_one(p, f))(frame)
        return apply_one(p, frame)

    in_info = TensorsInfo.make("uint8", f"3:{hw}:{hw}")
    out_info = TensorsInfo.make("float32", str(6 * k + 1)) if want_packed \
        else TensorsInfo.make(
            "float32,float32,float32,float32", f"4:{k},{k},{k},1")
    return apply_fn, params, in_info, out_info


class PoseNet(nn.Module):
    """Heatmap pose head over the /16 backbone (17 COCO keypoints)."""

    keypoints: int = 17
    width: float = 1.0

    @nn.compact
    def __call__(self, x):
        feat = _Backbone(width=self.width, max_stride=16)(x)
        hm = nn.Conv(self.keypoints, (1, 1), dtype=jnp.bfloat16)(feat)
        return jax.nn.sigmoid(hm.astype(jnp.float32))


@register_model("posenet")
def _build_posenet(width: str = "1.0", size: str = "257",
                   keypoints: str = "17", seed: str = "0",
                   decode: str = "0"):
    """``decode=device`` folds per-keypoint argmax into the XLA program
    and emits [K, 3] (x, y, score; normalized, pose-decoder "key" form)
    instead of the [H', W', K] heatmap — ~100x less D2H traffic and no
    host-side argmax. The decoder's heatmap mode stays the parity path
    (≙ tensordec-pose.c consumes raw heatmaps); this is the TPU-first
    option, like deeplab's argmax=u8."""
    w, hw, kp = float(width), int(size), int(keypoints)
    want_decode = decode not in ("0", "", "false")
    model = PoseNet(keypoints=kp, width=w)
    dummy = jnp.zeros((1, hw, hw, 3), jnp.bfloat16)
    params = jit_init(model, seed, dummy)

    def keypoints_of(hm):
        hp, wp, k = hm.shape
        flat = hm.reshape(-1, k)
        idx = jnp.argmax(flat, axis=0)
        ys = (idx // wp).astype(jnp.float32) / max(hp - 1, 1)
        xs = (idx % wp).astype(jnp.float32) / max(wp - 1, 1)
        scores = jnp.take_along_axis(flat, idx[None], axis=0)[0]
        return jnp.stack([xs, ys, scores], axis=1)  # [K, 3]

    def apply_fn(p, frame):
        batched = frame.ndim == 4
        x = frame.astype(jnp.bfloat16) / 127.5 - 1.0
        out = model.apply(p, x if batched else x[None])
        if want_decode:
            out = jax.vmap(keypoints_of)(out)
        return out if batched else out[0]

    hm = hw // 16 + (1 if hw % 16 else 0)
    in_info = TensorsInfo.make("uint8", f"3:{hw}:{hw}")
    out_info = TensorsInfo.make("float32", f"3:{kp}") if want_decode \
        else TensorsInfo.make("float32", f"{kp}:{hm}:{hm}")
    return apply_fn, params, in_info, out_info


class DeepLabV3(nn.Module):
    """ASPP-lite segmentation over the /16 backbone, logits upsampled
    in-graph to input resolution (the HBM-stress BASELINE config)."""

    num_classes: int = 21
    width: float = 1.0
    out_size: int = 257

    @nn.compact
    def __call__(self, x):
        feat = _Backbone(width=self.width, max_stride=16)(x)
        # ASPP-lite: 1x1 + global-pool branches (tflite-deeplab style)
        b0 = ConvBN(256)(feat)
        gp = jnp.mean(feat, axis=(1, 2), keepdims=True)
        gp = ConvBN(256)(gp)
        gp = jnp.broadcast_to(gp, b0.shape)
        h = ConvBN(256)(jnp.concatenate([b0, gp], axis=-1))
        logits = nn.Conv(self.num_classes, (1, 1),
                         dtype=jnp.float32)(h.astype(jnp.float32))
        return jax.image.resize(
            logits, (logits.shape[0], self.out_size, self.out_size,
                     self.num_classes), method="bilinear")


@register_model("deeplab_v3")
def _build_deeplab(width: str = "1.0", size: str = "257",
                   num_classes: str = "21", seed: str = "0",
                   argmax: str = "0"):
    """``argmax=1`` folds the per-pixel argmax into the XLA program and
    emits the int32 [H, W] class map instead of [H, W, C] logits — 21x
    less D2H traffic; ``argmax=u8`` goes further and emits uint8 (class
    count is <=255 by construction), another 4x off the host link.
    image_segment consumes any form (like the tflite deeplab variants
    that end in ArgMax)."""
    w, hw, nc = float(width), int(size), int(num_classes)
    want_argmax = argmax not in ("0", "", "false")
    argmax_dtype = jnp.uint8 if argmax == "u8" else jnp.int32
    if argmax == "u8" and nc > 255:
        raise ValueError(
            f"deeplab_v3: argmax=u8 cannot represent {nc} classes; "
            "use argmax=1 (int32)")
    model = DeepLabV3(num_classes=nc, width=w, out_size=hw)
    dummy = jnp.zeros((1, hw, hw, 3), jnp.bfloat16)
    params = jit_init(model, seed, dummy)

    def apply_fn(p, frame):
        batched = frame.ndim == 4
        x = frame.astype(jnp.bfloat16) / 127.5 - 1.0
        out = model.apply(p, x if batched else x[None])
        if want_argmax:
            out = jnp.argmax(out, axis=-1).astype(argmax_dtype)
        return out if batched else out[0]

    in_info = TensorsInfo.make("uint8", f"3:{hw}:{hw}")
    out_info = TensorsInfo.make(
        "uint8" if argmax == "u8" else "int32", f"{hw}:{hw}") \
        if want_argmax else TensorsInfo.make("float32", f"{nc}:{hw}:{hw}")
    return apply_fn, params, in_info, out_info
