"""Custom TPU kernels (Pallas) for the pipeline's hot host-boundary ops.

≙ the role of the reference's Orc SIMD acceleration in tensor_transform
(gsttensor_transform.c:56-57 HAVE_ORC) — hand-tuned inner loops for the
per-element math that wraps every model invoke. Here the hand-tuning
targets the TPU's VPU via Pallas; every op carries a jnp reference
implementation as the parity oracle. Off the TPU the kernel body runs
through the Pallas interpreter, never the reference in its place.

The models' kernels live beside them and are imported by their modules:
``sparse_attention`` (``nns_masked_attention``: causal, masked and
windowed attention a block of queries at a time), ``grouped`` (the
routed experts' product: the kernel ``nns_grouped_swiglu`` where a chip
holds at least half of a router, ``2 x held >= router width``; for any
smaller share the split form, ``nns_grouped_gate_up`` then
``nns_grouped_down``), ``kda`` (``nns_kda_chunk_intra``
/ ``nns_kda_chunk_state``: the chunked gated delta rule, a state
carried from chunk to chunk) and ``power_retention``
(``nns_power_retention``: the chunked gated power retention of degree
2, a state in and a state out, so that the jax filter can carry it from
one buffer of a document to the next).
"""
from .normalize import fused_normalize, normalize_reference

__all__ = ["fused_normalize", "normalize_reference"]
