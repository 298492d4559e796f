"""Model zoo: named model builders for the jax filter backend.

``model=zoo://<name>?k=v`` resolves here. A builder returns
``(apply_fn, params, input_info, output_info)`` where ``apply_fn(params,
*inputs)`` is a pure jittable function over *unbatched* frame tensors
(builders add/remove the batch dim internally so pipeline caps stay
per-frame, matching the reference's per-buffer invoke model). A model
that carries a state from buffer to buffer returns a fifth item, the
state before its first buffer, and its ``apply_fn(params, state,
*inputs)`` gives ``(outputs, state)`` (``filters/jax_backend.py``).

Params default to deterministic random init (seed in kwargs); pass
``params_dir=<orbax dir>`` to load trained weights.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

from ..tensors.info import TensorsInfo

Builder = Callable[..., Tuple[Callable, Any, Optional[TensorsInfo], Optional[TensorsInfo]]]

_ZOO: Dict[str, Builder] = {}


def register_model(name: str):
    def deco(fn: Builder) -> Builder:
        _ZOO[name] = fn
        return fn
    return deco


def build(name: str, params_dir: Optional[str] = None, **kwargs):
    if name not in _ZOO:
        raise ValueError(f"unknown zoo model {name!r}; known: {sorted(_ZOO)}")
    # four items, or five with the state before the first buffer
    apply_fn, params, *rest = _ZOO[name](**kwargs)
    if params_dir is not None:
        from ..trainers.checkpoint import restore_params
        params = restore_params(params_dir, params)
    return (apply_fn, params, *rest)


def model_names():
    return sorted(_ZOO)


def jit_init(model, seed: str, dummy):
    """Init a flax module's params in ONE compiled dispatch.

    Eager flax init runs hundreds of tiny ops, each its own compile
    and dispatch. Jitting the init collapses it into one.
    """
    import jax
    return jax.jit(model.init)(jax.random.PRNGKey(int(seed)), dummy)


@register_model("toyseg")
def _build_toyseg(height: str = "8", width: str = "8", classes: str = "5",
                  seed: str = "0"):
    """Toy per-pixel segmenter: [H, W] float32 -> [H, W, C] logits via
    per-class elementwise scale+shift. Deliberately elementwise-only
    (no matmul/conv, no reductions) so its outputs are bit-exact across
    XLA fusion decisions — the model the fusion compiler's byte-parity
    oracle leans on for filter->decoder chains."""
    import jax
    import jax.numpy as jnp

    h, w, c = int(height), int(width), int(classes)
    k1, k2 = jax.random.split(jax.random.PRNGKey(int(seed)))
    params = {
        "scale": jax.random.normal(k1, (c,), jnp.float32),
        "shift": jax.random.normal(k2, (c,), jnp.float32),
    }

    def apply_fn(p, x):
        return x.astype(jnp.float32)[..., None] * p["scale"] + p["shift"]

    in_info = TensorsInfo.make("float32", f"{h}:{w}")
    out_info = TensorsInfo.make("float32", f"{h}:{w}:{c}")
    return apply_fn, params, in_info, out_info


@register_model("toyscale")
def _build_toyscale(height: str = "8", width: str = "8", classes: str = "5",
                    seed: str = "1"):
    """Elementwise per-class affine over [H, W, C] logits -> [H, W, C]
    (a toy calibration head). Chains after ``toyseg`` as the second
    link of the fusion byte-parity oracle: elementwise-only like
    toyseg, so a toyseg!toyscale segment stays bit-exact across XLA
    fusion AND mesh partitioning decisions."""
    import jax
    import jax.numpy as jnp

    h, w, c = int(height), int(width), int(classes)
    k1, k2 = jax.random.split(jax.random.PRNGKey(int(seed)))
    params = {
        "scale": jax.random.normal(k1, (c,), jnp.float32),
        "shift": jax.random.normal(k2, (c,), jnp.float32),
    }

    def apply_fn(p, x):
        return x.astype(jnp.float32) * p["scale"] + p["shift"]

    info = TensorsInfo.make("float32", f"{h}:{w}:{c}")
    return apply_fn, params, info, info.copy()


@register_model("mlp")
def _build_mlp(in_dim: str = "64", hidden: str = "128", out_dim: str = "10",
               seed: str = "0", dtype: str = "bfloat16"):
    """Tiny MLP — the zoo's passthrough-grade test model."""
    import jax
    import jax.numpy as jnp

    d_in, d_h, d_out = int(in_dim), int(hidden), int(out_dim)
    k1, k2 = jax.random.split(jax.random.PRNGKey(int(seed)))
    dt = jnp.dtype(dtype)
    params = {
        "w1": jax.random.normal(k1, (d_in, d_h), dt) * (1.0 / d_in) ** 0.5,
        "b1": jnp.zeros((d_h,), dt),
        "w2": jax.random.normal(k2, (d_h, d_out), dt) * (1.0 / d_h) ** 0.5,
        "b2": jnp.zeros((d_out,), dt),
    }

    def apply_fn(p, x):
        x = x.astype(dt)
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        return (h @ p["w2"] + p["b2"]).astype(jnp.float32)

    in_info = TensorsInfo.make("float32", str(d_in))
    out_info = TensorsInfo.make("float32", str(d_out))
    return apply_fn, params, in_info, out_info
