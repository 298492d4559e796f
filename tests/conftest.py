"""Test configuration: force an 8-device virtual CPU mesh.

Tests run on CPU (fast compiles, no accelerator needed) with 8 virtual
devices so multi-chip sharding paths are exercised exactly as the
driver's dryrun_multichip does. Must run before jax is imported anywhere.
"""
import os

# force-set (not setdefault): tests never run on an accelerator, whatever
# the invoking shell exported
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# This environment's XLA CPU defaults to a reduced-precision matmul path
# (~4e-3 error on f32 dots), which breaks exactness-style assertions
# (decode-vs-forward, ring-vs-dense). Pin f32 matmuls for tests only;
# production keeps the platform default (bf16 passes on the TPU MXU).
import jax  # noqa: E402  (env vars above must be set first)

jax.config.update("jax_default_matmul_precision", "float32")
