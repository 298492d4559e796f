"""Multi-chip dryrun: one sharded train step and one sharded serve round.

Runs on the devices this process has: the four chips of a TPU host, or —
only when the process was told to run on the CPU (``JAX_PLATFORMS=cpu``)
— ``n_devices`` virtual host devices, which is how the tests and a
sandbox without an accelerator validate that the dp/sp/tp sharding
story compiles and executes.

Virtual devices exist only if ``XLA_FLAGS`` asks for them before JAX
initialises a backend; :func:`ensure_devices` sets the flag when it
still can and otherwise raises, naming the fresh-subprocess way out
(``__graft_entry__.dryrun_multichip`` takes it).
"""
from __future__ import annotations

import os
import re

_SUBPROCESS_HINT = (
    "run the dryrun in a fresh subprocess instead: "
    "`JAX_PLATFORMS=cpu python -m nnstreamer_tpu.parallel.dryrun <n>` "
    "(what __graft_entry__.dryrun_multichip does)")


def ensure_devices(n_devices: int) -> None:
    """Make sure this process's JAX has >= n_devices devices, or raise.

    On an accelerator host that is simply what ``jax.devices()``
    reports. On the CPU, and only while no backend exists yet (after
    initialisation the device-count flag is a silent no-op), ask XLA
    for ``n_devices`` virtual host devices first.
    """
    from jax._src import xla_bridge
    flags = os.environ.get("XLA_FLAGS", "")
    if os.environ.get("JAX_PLATFORMS") == "cpu" \
            and not xla_bridge.backends_are_initialized() \
            and not re.search(r"xla_force_host_platform_device_count=",
                              flags):
        os.environ["XLA_FLAGS"] = (
            flags +
            f" --xla_force_host_platform_device_count={n_devices}").strip()
    import jax
    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"ensure_devices({n_devices}): this process has "
            f"{len(devices)} {devices[0].platform} device(s); virtual "
            f"CPU devices need XLA_FLAGS="
            f"--xla_force_host_platform_device_count set before JAX "
            f"initialises a backend — " + _SUBPROCESS_HINT)


def train_step(mesh) -> float:
    """One sharded train step (forward + backward + optimizer; ring
    attention, then Ulysses, when the mesh has a seq axis) of a tiny
    decoder on ``mesh``; returns the loss."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from nnstreamer_tpu.models import transformer as tfm
    from nnstreamer_tpu.parallel import GPT_RULES
    from nnstreamer_tpu.parallel.train import (create_train_state,
                                               make_train_step, shard_batch)

    dp, sp, tp = (mesh.shape[a] for a in mesh.axis_names)
    cfg = tfm.GPTConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, mesh=mesh,
                        seq_axis="seq" if sp > 1 else None)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    optimizer = optax.adamw(1e-3)
    state = create_train_state(params, optimizer, mesh, GPT_RULES)

    seq = 8 * sp  # divisible by the seq axis for ring attention blocks
    batch = jnp.zeros((2 * dp, seq + 1), jnp.int32)
    batch = shard_batch(batch, mesh, P("data", None))

    step = make_train_step(lambda p, b: tfm.loss_fn(p, b, cfg), optimizer)
    state, loss = step(state, batch)
    loss.block_until_ready()
    assert jnp.isfinite(loss), f"non-finite loss {loss}"
    schemes = "ring" if sp > 1 else "none"
    if sp > 1 and (cfg.n_heads // tp) % sp == 0:
        # same step through the OTHER sequence-parallel scheme, so the
        # driver validates both collective patterns compile + execute
        import dataclasses
        cfg_u = dataclasses.replace(cfg, seq_axis="seq",
                                    seq_scheme="ulysses")
        loss_u = tfm.loss_fn(state.params, batch, cfg_u)
        loss_u.block_until_ready()
        assert jnp.isfinite(loss_u), f"non-finite ulysses loss {loss_u}"
        schemes = "ring+ulysses"
    platform = mesh.devices.ravel()[0].platform
    print(f"dryrun_multichip: {platform} mesh dp={dp} sp={sp} tp={tp} "
          f"seq={schemes} loss={float(loss):.4f} train ok", flush=True)
    return float(loss)


def run(n_devices: int) -> float:
    """The train step on :func:`best_mesh` of ``n_devices``, then the
    sharded serve round."""
    ensure_devices(n_devices)
    from nnstreamer_tpu.parallel.mesh import best_mesh

    loss = train_step(best_mesh(n_devices))
    run_infer(n_devices)
    return loss


def run_infer(n_devices: int) -> None:
    """Sharded *inference* round on the same devices (the BASELINE
    config-5 story): several query clients stream
    distinct frames to ONE server whose serversrc micro-batches them
    (batch=4) into shared stacked invokes of a mesh-mode mobilenet
    (batch dim on the ``data`` axis, params placed by rule table), and
    the serversink row-routes replies back. Asserts (a) micro-batching
    actually happened (< one invoke per frame and a stacked signature
    compiled), (b) every client got ITS OWN frames' answers, in order,
    bit-matching a single-device reference."""
    ensure_devices(n_devices)
    import socket
    import threading
    import time

    import numpy as np

    from nnstreamer_tpu import Buffer, parse_launch
    from nnstreamer_tpu.filters import FilterProperties, find_filter

    size = 96  # real conv stack, sized for the virtual CPU mesh
    zoo = f"zoo://mobilenet_v2?size={size}"
    caps = ('"other/tensors,format=static,num_tensors=1,'
            f'types=(string)uint8,dimensions=(string)3:{size}:{size},'
            'framerate=(fraction)0/1"')
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    dp = max(1, n_devices // 2)
    server = parse_launch(
        f"tensor_query_serversrc name=qs port={port} id=42 batch=4 "
        f"! tensor_filter name=f framework=jax model={zoo} "
        f'custom="mesh:{dp}x1x2" prefetch-host=true '
        f"! tensor_query_serversink id=42")
    server.start()
    time.sleep(0.2)

    ref = find_filter("jax")()
    ref.open(FilterProperties(framework="jax", model_files=(zoo,)))
    n_clients, frames_each = 3, 4
    rng = np.random.default_rng(7)
    xs = {(c, i): rng.integers(0, 255, (size, size, 3), np.uint8,
                               endpoint=True)
          for c in range(n_clients) for i in range(frames_each)}
    want = {k: np.asarray(ref.invoke([v])[0]) for k, v in xs.items()}
    ref.close()

    results: dict = {}

    def client(c):
        # jittered starts: clients must interleave mid-stream (not line
        # up batch-aligned), so the order assertion below exercises the
        # row router against mixed-client batches
        time.sleep(0.03 * c)
        cl = parse_launch(
            f"appsrc name=in caps={caps} "
            f"! tensor_query_client port={port} timeout=60 max-request=8 "
            "! appsink name=out")
        cl.start()
        for i in range(frames_each):
            cl["in"].push_buffer(Buffer.from_arrays([xs[(c, i)]]))
        deadline = time.monotonic() + 300
        while len(cl["out"].buffers) < frames_each \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        results[c] = [np.asarray(b.chunks[0].host()).copy()
                      for b in cl["out"].buffers]
        cl["in"].end_stream()
        cl.stop()

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=320)
    n_invokes = server["f"]._invoke_count
    sigs = list(server["f"].fw._jit_cache)
    server.stop()
    total = n_clients * frames_each
    import math
    # a perfectly coalescing server needs ceil(total/4) stacked
    # invokes; +2 tolerates ragged head/tail batches from the jittered
    # client starts. More than that means micro-batching degraded to
    # near-per-frame dispatch (the regression this guard exists for).
    bound = math.ceil(total / 4) + 2
    assert n_invokes <= bound, \
        f"micro-batching degraded: {n_invokes} invokes for {total} " \
        f"frames (bound {bound})"
    assert any(sig and sig[0][0] and sig[0][0][0] == 4 for sig in sigs), \
        f"no stacked (batch=4) signature compiled: {sigs}"
    for c in range(n_clients):
        got = results.get(c, [])
        assert len(got) == frames_each, \
            f"client {c} got {len(got)}/{frames_each} replies"
        for i, arr in enumerate(got):
            np.testing.assert_allclose(
                arr, want[(c, i)], rtol=1e-4, atol=1e-4,
                err_msg=f"row-routing broke for client {c} frame {i}")
    print(f"dryrun_multichip: mesh dp={dp} tp=2 query micro-batch=4 "
          f"clients={n_clients} invokes={n_invokes}/{total} "
          "row-routing infer ok", flush=True)


if __name__ == "__main__":  # python -m nnstreamer_tpu.parallel.dryrun N
    import sys

    run(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
