"""The precisions the references run in. ``f32``: float32 with
``highest`` matmul precision (on a TPU a float32 matmul otherwise runs in
bfloat16 passes). ``fp8``: the control, the nearest precision below the
bfloat16 the configurations state: both operands of every matmul rounded
to float8_e4m3 (``fp8_e5m2``: float8_e5m2) with a per-tensor scale, the product accumulated in
float32: the weight products and attention's two products alike."""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = {"fp8": (jnp.float8_e4m3fn, 448.0),         # 3 bits of mantissa
       "fp8_e5m2": (jnp.float8_e5m2, 57344.0)}    # 2 bits of mantissa


def fp8_round(x, kind="fp8"):
    dtype, top = FP8[kind]
    x = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = top / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def make_prep(precision: str):
    """What a matrix product's operand goes through first: a cast to
    float32, or the control's rounding to fp8."""
    if precision == "f32":
        return lambda x: x.astype(jnp.float32)
    if precision in FP8:
        return lambda x: fp8_round(x, precision)
    raise ValueError(f"unknown reference precision {precision!r}")


def make_dot(precision: str):
    """``dot(a, b)`` contracting a's last with b's first dimension."""
    prep = make_prep(precision)

    def dot(a, b):
        return jnp.tensordot(prep(a), prep(b), axes=1,
                             precision=jax.lax.Precision.HIGHEST)

    return dot
