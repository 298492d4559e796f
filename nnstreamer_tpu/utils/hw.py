"""Hardware capability probe.

≙ gst/nnstreamer/hw_accel.c (NEON/SIMD detection via getauxval) — the
TPU-native version surfaces the accelerator fleet (jax.devices(): kind,
count, per-device memory stats) alongside host SIMD flags from
/proc/cpuinfo, and answers the filter ABI's CHECK_HW_AVAILABILITY
event.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List


@functools.lru_cache(maxsize=1)
def cpu_simd_flags() -> List[str]:
    """Host vector-ISA flags (≙ accl_available neon/sse checks)."""
    wanted = {"neon", "asimd", "sse", "sse2", "sse4_1", "sse4_2",
              "avx", "avx2", "avx512f", "amx_tile"}
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith(("flags", "features")):
                    present = set(line.split(":", 1)[1].split())
                    return sorted(wanted & present)
    except OSError:
        pass
    return []


def accelerators() -> List[Dict[str, Any]]:
    """One entry per jax device: platform/kind/id + memory stats when
    the backend exposes them (TPU HBM usage)."""
    import jax
    out = []
    for d in jax.devices():
        entry: Dict[str, Any] = {
            "id": d.id,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", ""),
            "process_index": d.process_index,
        }
        try:
            stats = d.memory_stats()
            if stats:
                entry["memory"] = {
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                }
        except Exception:  # noqa: BLE001 -- optional per backend
            pass
        out.append(entry)
    return out


def capabilities() -> Dict[str, Any]:
    """Full probe result; cheap after the first call (jax caches its
    backend)."""
    accs = accelerators()
    return {
        "accelerators": accs,
        "num_devices": len(accs),
        "default_platform": accs[0]["platform"] if accs else "none",
        "cpu_simd": cpu_simd_flags(),
    }


# Peak figures per JAX DEVICE, keyed by device-kind substring (checked
# in order). Source: Google Cloud TPU documentation, per-chip figures —
# v2 45 TFLOP/s / 700 GB/s, v3 123 / 900, v4 275 / 1228, v5e 197 / 819,
# v5p 459 / 2765, v6e 918 / 1640. On v2/v3 jax.devices() enumerates
# TensorCores (2 per chip) and a single-device jit runs on ONE core, so
# those rows carry the per-core half. A kind that matches no row is an
# error, not a default: a utilization over a guessed peak is a made-up
# number.
_PEAKS = (
    # (kind substring, peak dense bf16 TFLOP/s, peak HBM GB/s)
    ("v6 lite", 918.0, 1640.0), ("v6e", 918.0, 1640.0),
    ("v5 lite", 197.0, 819.0), ("v5litepod", 197.0, 819.0),
    ("v5e", 197.0, 819.0),
    ("v5p", 459.0, 2765.0),
    ("v4", 275.0, 1228.0), ("v3", 61.5, 450.0), ("v2", 22.5, 350.0),
)


def _peaks(device):
    import jax
    d = device if device is not None else jax.devices()[0]
    kind = getattr(d, "device_kind", "")
    if d.platform == "tpu":
        lowered = kind.lower()
        for sub, tflops, gbps in _PEAKS:
            if sub in lowered:
                return tflops * 1e12, gbps * 1e9
    raise ValueError(
        f"no peak figures for device kind {kind!r} (platform "
        f"{d.platform}); add its row to utils/hw.py _PEAKS with its "
        f"source")


def peak_flops(device=None) -> float:
    """Peak dense bf16 FLOP/s for ``device`` (default: first jax
    device). Raises ValueError for a kind the table does not know."""
    return _peaks(device)[0]


def peak_membw(device=None) -> float:
    """Peak HBM bytes/s for ``device`` (default: first jax device) —
    the denominator for decode-phase bandwidth utilization. Raises
    ValueError for a kind the table does not know."""
    return _peaks(device)[1]


def is_available(kind: str) -> bool:
    """CHECK_HW_AVAILABILITY answer: is an accelerator of this kind
    (``tpu``/``gpu``/``cpu``/``default``) usable?"""
    import jax
    kind = (kind or "default").lower()
    if kind in ("default", "any"):
        return True
    if kind in ("cpu", "gpu", "tpu"):
        # ask the named backend directly: jax.devices() only lists the
        # default platform, so a TPU host would wrongly report no CPU
        try:
            return len(jax.devices(kind)) > 0
        except RuntimeError:
            return False
    return any(a["platform"].lower() == kind or
               kind in a["kind"].lower() for a in accelerators())
