"""Device-mesh construction helpers.

The mesh axes follow the scaling-book convention: ``data`` (batch /
fully-replicated gradients via psum), ``seq`` (sequence/context
parallelism — ring attention neighbors should be ICI neighbors), and
``model`` (tensor parallelism). Multi-host meshes come from
``jax.devices()`` spanning hosts; XLA routes collectives over ICI within a
slice and DCN across slices.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXES = ("data", "seq", "model")

# one Mesh object per (logical shape, device set): a serving filter and a
# colocated trainer declaring the same spec get the SAME mesh — one device
# pool, two workloads, neither evicting the other's params (train/serve
# colocation). Mesh is immutable, so sharing is safe across threads.
_SHARED: Dict[Tuple, Mesh] = {}
_SHARED_LOCK = threading.Lock()


def make_mesh(shape: Sequence[int], axis_names: Sequence[str] = AXES,
              devices=None) -> Mesh:
    """Mesh of the given logical shape; devices default to all local."""
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh {tuple(shape)} needs {n} devices, "
                         f"have {len(devices)}")
    # topology-aware on TPU (mesh neighbours are ICI neighbours: a v5e
    # 2x2 host comes back in ring order 0,1,3,2), a plain reshape on
    # CPU/virtual devices. What it refuses — 3 of a 2x2 host's chips,
    # by assertion — is an error here too: a mesh silently laid out
    # against the topology is not the mesh that was asked for
    try:
        arr = mesh_utils.create_device_mesh(tuple(shape), devices[:n])
    except (AssertionError, NotImplementedError, ValueError) as exc:
        raise ValueError(
            f"mesh {tuple(shape)} cannot be laid out on {n} of this "
            f"host's {len(devices)} {devices[0].platform} devices: "
            f"{exc}") from exc
    return Mesh(arr, tuple(axis_names))


def spec_dims(spec: str) -> Optional[Tuple[int, int, int]]:
    """Parse an explicit ``"DxSxT"`` spec into (dp, sp, tp) without
    touching devices; None for ``auto``/``true``/empty (device-count
    dependent) or anything unparseable."""
    if not spec or spec in ("auto", "true"):
        return None
    try:
        dims = [int(d) for d in str(spec).lower().split("x")]
    except ValueError:
        return None
    if not dims or any(d < 1 for d in dims):
        return None
    while len(dims) < 3:
        dims.append(1)
    return tuple(dims[:3])  # type: ignore[return-value]


def spec_dp(spec: str) -> int:
    """The data-parallel factor a spec declares: parsed statically for
    explicit specs (no device access — safe for lint/admission code);
    ``auto`` consults the backend via :func:`best_mesh` (and raises
    with it when there is none); anything empty or unparseable is 1
    (no snapping, no sharding)."""
    dims = spec_dims(spec)
    if dims is not None:
        return dims[0]
    if spec in ("auto", "true"):
        return factorization(best_mesh())[0]
    return 1


def mesh_from_spec(spec: str) -> Mesh:
    """Element-property mesh grammar: ``"2x2x2"`` -> Mesh(dp=2, sp=2,
    tp=2); missing trailing factors default to 1; ``"auto"``/``"true"``
    factors all visible devices via :func:`best_mesh`. Resolved meshes
    are shared: two elements declaring the same spec over the same
    device set (a serving filter and a colocated trainer, a serve src
    and its downstream filter) get one Mesh object."""
    if spec in ("auto", "true"):
        return shared_mesh(factorization(best_mesh()))
    dims = spec_dims(spec)
    if dims is None:
        raise ValueError(f"unparseable mesh spec {spec!r} "
                         f"(want 'DxSxT', 'auto' or 'true')")
    return shared_mesh(dims)


def shared_mesh(dims: Sequence[int]) -> Mesh:
    """The process-wide shared Mesh for a logical shape over the default
    device set (see module docstring on colocation)."""
    dims = tuple(int(d) for d in dims)
    key = (dims, tuple((d.platform, d.id) for d in jax.devices()))
    with _SHARED_LOCK:
        mesh = _SHARED.get(key)
        if mesh is None:
            mesh = _SHARED[key] = make_mesh(dims)
        return mesh


def best_mesh(n_devices: Optional[int] = None, model_parallel: int = 0,
              seq_parallel: int = 0) -> Mesh:
    """Factor n into (data, seq, model).

    Defaults: model axis gets 2 when n is even (exercises tp collectives),
    seq gets 2 when 4 | n, data takes the rest. Explicit sizes override.
    """
    n = n_devices if n_devices is not None else len(jax.devices())
    tp = model_parallel or (2 if n % 2 == 0 else 1)
    rest = n // tp
    sp = seq_parallel or (2 if rest % 2 == 0 and rest >= 2 else 1)
    dp = rest // sp
    if dp * sp * tp != n:
        raise ValueError(f"cannot factor {n} into dp*sp*tp = {dp}*{sp}*{tp}")
    return make_mesh((dp, sp, tp))


def factorization(mesh: Mesh) -> Tuple[int, int, int]:
    return tuple(mesh.shape[a] for a in mesh.axis_names)  # type: ignore
