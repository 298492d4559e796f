"""Custom TPU kernels (Pallas) for the pipeline's hot host-boundary ops.

≙ the role of the reference's Orc SIMD acceleration in tensor_transform
(gsttensor_transform.c:56-57 HAVE_ORC) — hand-tuned inner loops for the
per-element math that wraps every model invoke. Here the hand-tuning
targets the TPU's VPU via Pallas; every op carries a jnp reference
implementation as the parity oracle. Off the TPU the kernel body runs
through the Pallas interpreter, never the reference in its place.
"""
from .normalize import fused_normalize, normalize_reference

__all__ = ["fused_normalize", "normalize_reference"]
