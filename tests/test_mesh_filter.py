"""Mesh-mode JaxFilter: multi-chip invoke in the *pipeline* layer.

The reference fans inference streams across devices via tensor_query
(ref: gst/nnstreamer/tensor_query/README.md:5-27); the TPU-native design
additionally lets one tensor_filter invoke fan out over a device mesh —
params sharded by rule table, batch sharded over the ``data`` axis, XLA
collectives over ICI. These tests run on the 8-virtual-device CPU mesh
(conftest.py) exactly like the driver's dryrun.
"""
import socket
import threading
import time

import numpy as np
import pytest

import jax

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.filters import FilterProperties, find_filter

CAPS8x64 = ("other/tensors,format=static,num_tensors=1,"
            "types=(string)float32,dimensions=(string)64:8,framerate=0/1")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _open_filter(custom=""):
    fw = find_filter("jax")()
    fw.open(FilterProperties(framework="jax",
                             model_files=("zoo://mlp?dtype=float32",),
                             custom_properties=custom))
    return fw


def test_mesh_invoke_matches_single_device():
    x = np.random.RandomState(0).randn(8, 64).astype(np.float32)
    ref = _open_filter()
    want = np.asarray(ref.invoke([x])[0])
    ref.close()

    fw = _open_filter("mesh:4x1x2,rules:gpt")
    out = fw.invoke([x])[0]
    # batch rides the data axis: the invoke really fanned out over chips
    assert len(out.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    fw.close()


def test_mesh_invoke_indivisible_batch_replicates():
    fw = _open_filter("mesh:4x1x2,rules:gpt")
    x = np.random.RandomState(1).randn(3, 64).astype(np.float32)
    out = np.asarray(fw.invoke([x])[0])
    assert out.shape == (3, 10)
    fw.close()


def test_mesh_suspend_resume_keeps_sharding():
    from nnstreamer_tpu.filters.base import FilterEvent
    x = np.random.RandomState(2).randn(8, 64).astype(np.float32)
    fw = _open_filter("mesh:4x1x2,rules:gpt")
    want = np.asarray(fw.invoke([x])[0])
    assert fw.handle_event(FilterEvent.SUSPEND)
    got = fw.invoke([x])[0]  # transparent resume
    assert len(got.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    fw.close()


def test_pipeline_mesh_filter_matches_single_device():
    """A *pipeline* on the 8-device mesh whose sharded invoke output equals the single-device output."""
    x = np.random.RandomState(3).randn(8, 64).astype(np.float32)

    def run(custom):
        opt = f" custom={custom}" if custom else ""
        p = parse_launch(
            f'appsrc name=in caps="{CAPS8x64}" '
            f'! tensor_filter framework=jax model=zoo://mlp?dtype=float32'
            f'{opt} ! appsink name=out')
        p.start()
        p["in"].push_buffer(Buffer.from_arrays([x]))
        p["in"].end_stream()
        assert p.wait_eos(timeout=30)
        p.stop()
        return np.asarray(p["out"].buffers[-1].chunks[0].host())

    single = run("")
    meshed = run("mesh:2x1x4,rules:gpt")
    np.testing.assert_allclose(meshed, single, rtol=1e-5, atol=1e-5)


def test_query_fanout_to_mesh_server():
    """BASELINE config 5 shape: multiple query clients feed one server
    pipeline whose filter holds ONE mesh-sharded model (workers share
    params; batch dim rides the data axis)."""
    port = _free_port()
    server = parse_launch(
        f'tensor_query_serversrc name=qs port={port} id=7 '
        '! tensor_filter framework=jax model=zoo://mlp?dtype=float32 '
        'custom=mesh:4x1x2,rules:gpt '
        '! tensor_query_serversink id=7')
    server.start()
    time.sleep(0.2)

    ref = _open_filter()
    xs = {i: np.random.RandomState(10 + i).randn(8, 64).astype(np.float32)
          for i in range(2)}
    want = {i: np.asarray(ref.invoke([xs[i]])[0]) for i in xs}
    ref.close()

    results = {}

    def run_client(tag):
        c = parse_launch(
            f'appsrc name=in caps="{CAPS8x64}" '
            f'! tensor_query_client port={port} timeout=20 '
            '! appsink name=out')
        c.start()
        c["in"].push_buffer(Buffer.from_arrays([xs[tag]]))
        deadline = time.monotonic() + 25
        while not c["out"].buffers and time.monotonic() < deadline:
            time.sleep(0.05)
        results[tag] = [np.asarray(b.chunks[0].host()).copy()
                        for b in c["out"].buffers]
        c["in"].end_stream()
        c.stop()

    threads = [threading.Thread(target=run_client, args=(i,)) for i in xs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=40)
    server.stop()
    for i in xs:
        assert len(results[i]) == 1, f"client {i} got {results[i]}"
        np.testing.assert_allclose(results[i][0], want[i],
                                   rtol=1e-4, atol=1e-4)


def test_query_microbatch_lands_sharded_on_mesh():
    """serversrc batch>1 stacks frames from several
    clients into ONE invoke whose batch dim rides the mesh data axis —
    batched invoke over ICI, not per-frame dispatch."""
    port = _free_port()
    server = parse_launch(
        f'tensor_query_serversrc name=qs port={port} id=8 batch=4 '
        '! tensor_filter name=f framework=jax '
        'model=zoo://mlp?dtype=float32 custom="mesh:4x1x2,rules:gpt" '
        '! tensor_query_serversink id=8')
    server.start()
    time.sleep(0.2)

    ref = _open_filter()
    n_frames = 6
    xs = {i: np.random.RandomState(30 + i).randn(8, 64).astype(np.float32)
          for i in range(n_frames)}
    want = {i: np.asarray(ref.invoke([xs[i]])[0]) for i in xs}
    ref.close()

    c = parse_launch(
        f'appsrc name=in caps="{CAPS8x64}" '
        f'! tensor_query_client port={port} timeout=20 max-request=8 '
        '! appsink name=out')
    c.start()
    for i in range(n_frames):
        c["in"].push_buffer(Buffer.from_arrays([xs[i]]))
    deadline = time.monotonic() + 40
    while len(c["out"].buffers) < n_frames and time.monotonic() < deadline:
        time.sleep(0.05)
    c["in"].end_stream()
    n_invokes = server["f"]._invoke_count
    fw = server["f"].fw
    # stacked signature reached the backend: some executable was compiled
    # for a leading batch dim of 4 (i.e. input (4, 8, 64))
    sigs = list(fw._jit_cache)
    c.stop()
    server.stop()
    out = c["out"].buffers
    assert len(out) == n_frames
    for i, b in enumerate(out):
        np.testing.assert_allclose(b.chunks[0].host(), want[i],
                                   rtol=1e-4, atol=1e-4)
    assert n_invokes < n_frames, (n_invokes, n_frames)
    assert any(sig[0][0] == (4, 8, 64) for sig in sigs), sigs


def test_filter_slices_padded_rows_of_host_outputs():
    """batch_valid_rows: padded micro-batch rows of HOST outputs are
    dropped (free numpy view) before they hit the wire; device outputs
    keep their padding (slicing them is one more eager device op — the
    serversink demux drops the rows instead)."""
    from nnstreamer_tpu.pipeline.registry import make_element
    from nnstreamer_tpu.tensors.buffer import Buffer as B, Chunk
    f = make_element("tensor_filter", framework="jax",
                     model="zoo://mlp?dtype=float32")
    got = []
    f.start()

    class HostFw:
        def invoke(self, inputs):
            return [np.ones((4, 10), np.float32)]

    f.fw = HostFw()
    f.srcpad.push = got.append  # capture without a downstream element
    x = np.random.RandomState(0).randn(4, 8, 64).astype(np.float32)
    buf = B([Chunk(x)])
    buf.extras["batch_valid_rows"] = 2
    buf.extras["batch_rows"] = [(0, 0, None), (1, 0, None)]
    f.do_chain(f.sinkpad, buf)
    f.fw = None
    f.stop()
    assert len(got) == 1
    assert got[0].chunks[0].shape[0] == 2  # padded rows 2..3 never ship


# ---------------------------------------------------- sharded serving

CAPS8x8 = ("other/tensors,format=static,num_tensors=1,"
           "types=(string)float32,dimensions=(string)8:8,framerate=0/1")


def _open_model(model, custom=""):
    fw = find_filter("jax")()
    fw.open(FilterProperties(framework="jax", model_files=(model,),
                             custom_properties=custom))
    return fw


def _sink_bytes(p, sink="out"):
    out = []
    for buf in p[sink].buffers:
        out.append(tuple(
            (str(np.asarray(c.host()).dtype), np.asarray(c.host()).shape,
             np.ascontiguousarray(c.host()).tobytes())
            for c in buf.chunks))
    return out


@pytest.mark.parametrize("model,shape", [
    ("zoo://mlp?dtype=float32", (64, 64)),
    ("zoo://toyseg", (64, 8, 8)),
])
def test_batch64_sharded_invoke_byte_identical(model, shape):
    """The serve path's parity contract: a batch-64 invoke laid out
    batch-major over the 8-device mesh is byte-identical to the
    single-chip invoke at zoo shapes (f32 matmul precision pinned by
    conftest)."""
    x = np.random.RandomState(7).randn(*shape).astype(np.float32)
    ref = _open_model(model)
    want = np.asarray(ref.invoke([x])[0])
    ref.close()
    fw = _open_model(model, "mesh:8x1x1")
    out = fw.invoke([x])[0]
    assert len(out.sharding.device_set) == 8
    assert np.asarray(out).tobytes() == want.tobytes()
    fw.close()


def test_fused_segment_on_mesh_byte_identical():
    """A fused run of two mesh-sharded members stays mesh-resident
    across the member boundary and is byte-identical to both the
    single-chip fused run and the unfused chain (elementwise oracle
    chain, like tools/fuse_parity.py uses)."""
    desc = ('tensortestsrc num-buffers=4 caps={caps} ! '
            'tensor_filter framework=jax model=zoo://toyseg {c} name=f1 ! '
            'tensor_filter framework=jax model=zoo://toyscale {c} name=f2 ! '
            'appsink name=out')

    def run(custom, fuse):
        p = parse_launch(desc.format(
            caps=CAPS8x8, c=f"custom={custom}" if custom else ""))
        p.fuse = fuse
        p.run(timeout=120)
        return p

    def segs(p):
        return [e for e in p.elements.values()
                if getattr(e, "IS_FUSED_SEGMENT", False)]

    plain = run("", fuse=False)
    fused = run("", fuse=True)
    meshed = run("mesh:8x1x1", fuse=True)
    sg = segs(meshed)
    assert len(sg) == 1, "mesh members did not fuse"
    assert sg[0].stats["fused_elements"] == 2
    assert sg[0].stats["devices"] == 8
    assert not segs(plain)
    a, b, c = _sink_bytes(plain), _sink_bytes(fused), _sink_bytes(meshed)
    assert len(a) == len(b) == len(c) == 4
    assert a == b == c, "sharded fused run is not byte-identical"


def test_mesh_spec_change_breaks_fused_run():
    """One fused program runs on one mesh: members declaring different
    mesh specs must not share a segment."""
    p = parse_launch(
        f'tensortestsrc num-buffers=2 caps={CAPS8x8} ! '
        'tensor_filter framework=jax model=zoo://toyseg '
        'custom=mesh:8x1x1 name=f1 ! '
        'tensor_filter framework=jax model=zoo://toyscale name=f2 ! '
        'appsink name=out')
    p.fuse = True
    p.run(timeout=120)
    assert not [e for e in p.elements.values()
                if getattr(e, "IS_FUSED_SEGMENT", False)]
    assert "mesh spec changes mid-run" in p._fusion_plan.vetoes["f2"]


def test_sharded_dispatch_occupies_one_window_slot():
    """The in-flight window budgets per MESH: one dispatched sharded
    batch takes one slot (one XLA dispatch), not len(mesh.devices)."""
    from nnstreamer_tpu.tensors.transfer import InFlightWindow
    w = InFlightWindow(2, devices=8)
    t1 = w.acquire()
    t2 = w.acquire()
    assert t1 is not None and t2 is not None
    # if slots were per-chip, 8-wide dispatches would leave 14 "free"
    assert w.acquire(timeout=0.05) is None
    rep = w.report()
    assert rep["window"] == 2
    assert rep["devices"] == 8
    assert rep["in_flight"] == 2
    w.release(t1)
    w.release(t2)
    assert w.idle()


def test_mesh_filter_window_reports_mesh_devices():
    """A windowed mesh filter's transfer_report carries the mesh span,
    and the dispatch/complete split stays correct: every frame settles
    through the window with the bytes the same sharded program gives
    when invoked synchronously."""
    x = np.random.RandomState(11).randn(8, 64).astype(np.float32)
    ref = _open_model("zoo://mlp?dtype=float32")
    single = np.asarray(ref.invoke([x])[0])
    ref.close()
    fw = _open_model("zoo://mlp?dtype=float32", "mesh:8x1x1")
    want = np.asarray(fw.invoke([x])[0])
    fw.close()
    p = parse_launch(
        f'appsrc name=in caps="{CAPS8x64}" '
        '! tensor_filter name=f framework=jax '
        'model=zoo://mlp?dtype=float32 custom=mesh:8x1x1 in-flight=2 '
        '! appsink name=out')
    p.start()
    for _ in range(4):
        p["in"].push_buffer(Buffer.from_arrays([x]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    rep = p["f"].transfer_report()
    got = _sink_bytes(p)
    p.stop()
    assert rep["devices"] == 8
    assert rep["window"] == 2
    assert rep["completed"] == 4
    assert len(got) == 4
    # the window changes when a frame is dispatched and completed, not
    # its program or its sharding: byte-equal to the synchronous invoke
    assert all(g[0][2] == want.tobytes() for g in got)
    # Across shardings XLA promises the same mathematics, not the same
    # bytes: at 8 rows over 8 devices each device multiplies a [1, 64]
    # row where the single chip multiplies [8, 64], and the CPU backend
    # sums the products of the two shapes in different orders (read:
    # 7.2e-7 on outputs up to 2.3 = 3 float32 ulps; at 8 rows a device,
    # test_batch64_sharded_invoke_byte_identical, the bytes agree).
    # 1e-5 of the largest output is 84 float32 ulps, room for a sum of
    # 128 products, and 800 times tighter than one bfloat16 ulp: a
    # lower precision, a dropped or a shifted row still fails.
    # tools/shard_parity.py holds its mesh pipelines to the same rule.
    assert np.abs(want - single).max() <= 1e-5 * np.abs(single).max()
