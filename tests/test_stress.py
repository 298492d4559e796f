"""Concurrency / race stress tests.

≙ the reference's race-detection strategy slot (SURVEY.md §5: it relies
on valgrind suppressions + CI static analysis + GStreamer's threading
model). Here the runtime's own locks are exercised directly: shared
models invoked from many pipelines at once, rapid start/stop cycles,
and concurrent registry mutation.
"""
import threading
import time

import numpy as np
import pytest

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters import register_custom_easy
from nnstreamer_tpu.tensors import TensorsInfo

CAPS = ("other/tensors,format=static,num_tensors=1,types=float32,"
        "dimensions=8,framerate=0/1")


@pytest.fixture(autouse=True)
def _fixtures():
    register_custom_easy(
        "id8", lambda x: x,
        TensorsInfo.make("float32", "8"), TensorsInfo.make("float32", "8"))
    yield


def test_parallel_pipelines_shared_model():
    """8 pipelines sharing one backend via shared-tensor-filter-key:
    one open, concurrent invokes, correct refcounted teardown."""
    def run_one(results, i):
        p = nt.parse_launch(
            f"tensortestsrc caps={CAPS} num-buffers=20 pattern=ones ! "
            "tensor_filter framework=custom-easy model=id8 "
            "shared-tensor-filter-key=stress ! appsink name=out")
        p.run(30)
        results[i] = len(p["out"].buffers)

    results = {}
    threads = [threading.Thread(target=run_one, args=(results, i))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert all(results.get(i) == 20 for i in range(8)), results
    from nnstreamer_tpu.filters.registry import _SHARED
    assert "stress" not in _SHARED  # last release closed it


def test_rapid_start_stop_cycles():
    for _ in range(15):
        p = nt.parse_launch(
            f"tensortestsrc caps={CAPS} num-buffers=3 ! "
            "queue max-size-buffers=2 ! fakesink")
        p.start()
        p.stop()  # stop mid-flight: must not deadlock or error fatally


def test_concurrent_registry_mutation_under_traffic():
    """Registering/unregistering custom filters while pipelines run."""
    from nnstreamer_tpu.filters import unregister_custom_easy
    stop = threading.Event()

    def churn():
        i = 0
        while not stop.is_set():
            register_custom_easy(
                f"churn{i % 4}", lambda x: x,
                TensorsInfo.make("float32", "8"),
                TensorsInfo.make("float32", "8"))
            unregister_custom_easy(f"churn{(i + 2) % 4}")
            i += 1

    t = threading.Thread(target=churn, daemon=True)
    t.start()
    try:
        for _ in range(5):
            p = nt.parse_launch(
                f"tensortestsrc caps={CAPS} num-buffers=10 ! "
                "tensor_filter framework=custom-easy model=id8 ! "
                "appsink name=out")
            p.run(20)
            assert len(p["out"].buffers) == 10
    finally:
        stop.set()
        t.join(5)


def test_leaky_downstream_eviction_multi_producer():
    """4 producers hammer one leaky=downstream queue whose consumer is
    slow: eviction must neither deadlock, nor drop EVENTS, nor corrupt
    the stream (newest data survives)."""
    from nnstreamer_tpu.pipeline.events import EosEvent
    from nnstreamer_tpu.pipeline.registry import make_element
    from nnstreamer_tpu.tensors.buffer import Buffer, Chunk

    q = make_element("queue", **{"max-size-buffers": 4,
                                 "leaky": "downstream"})
    sink = make_element("appsink")
    q.srcpad.link(sink.sinkpad)
    orig_render = sink.render

    def slow_render(buf):
        time.sleep(0.002)
        orig_render(buf)

    sink.render = slow_render
    sink.start()
    q.start()
    N, P = 100, 4
    errs = []

    def producer(tag):
        try:
            for i in range(N):
                q.chain(q.sinkpad, Buffer(
                    [Chunk(np.full(4, tag * 1000 + i, np.float32))]))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=producer, args=(i,))
               for i in range(P)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    q.chain(q.sinkpad, EosEvent())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not sink._eos_seen:
        time.sleep(0.01)
    q.stop()
    sink.stop()
    assert not errs
    assert sink._eos_seen             # events are never evicted
    got = len(sink.buffers)
    assert 0 < got < N * P            # leaky: some frames dropped, not all


def test_leaky_upstream_drop_multi_producer():
    """leaky=upstream with a stalled consumer: producers never block,
    and the queue stays bounded."""
    from nnstreamer_tpu.pipeline.registry import make_element
    from nnstreamer_tpu.tensors.buffer import Buffer, Chunk

    q = make_element("queue", **{"max-size-buffers": 2,
                                 "leaky": "upstream"})
    sink = make_element("appsink")
    q.srcpad.link(sink.sinkpad)
    stall = threading.Event()
    orig_render = sink.render

    def stalled_render(buf):
        stall.wait(5)
        orig_render(buf)

    sink.render = stalled_render
    sink.start()
    q.start()
    t0 = time.monotonic()
    for i in range(200):
        q.chain(q.sinkpad, Buffer([Chunk(np.zeros(2, np.float32))]))
    elapsed = time.monotonic() - t0
    stall.set()
    q.stop()
    sink.stop()
    assert elapsed < 2.0  # producers never waited on the stalled consumer


def test_mux_demux_under_start_stop_churn():
    """mux + demux pipeline started/stopped rapidly mid-stream: no
    deadlock, no error escalation, teardown always completes."""
    for _ in range(10):
        p = nt.parse_launch(
            "tensor_mux name=mux sync-mode=slowest ! "
            "tensor_demux name=d tensorpick=0,1 "
            f"tensortestsrc caps={CAPS} num-buffers=50 ! mux.sink_0 "
            f"tensortestsrc caps={CAPS} num-buffers=50 ! mux.sink_1 "
            "d.src_0 ! queue max-size-buffers=2 ! fakesink "
            "d.src_1 ! queue max-size-buffers=2 ! appsink name=out")
        p.start()
        time.sleep(0.02)  # stop mid-flight
        p.stop()


def test_native_ring_close_race():
    """Producers blocked in push() while the ring is being torn down
    (queue stop): must unblock, not crash, not hang."""
    from nnstreamer_tpu.native.lib import native_available
    if not native_available():
        pytest.skip("libnnstpu not built")
    from nnstreamer_tpu.pipeline.registry import make_element
    from nnstreamer_tpu.tensors.buffer import Buffer, Chunk

    for _ in range(10):
        q = make_element("queue", **{"max-size-buffers": 2,
                                     "backend": "native"})
        sink = make_element("fakesink")
        q.srcpad.link(sink.sinkpad)
        sink.start()
        q.start()
        done = threading.Event()

        def producer():
            try:
                for _ in range(50):
                    q.chain(q.sinkpad, Buffer(
                        [Chunk(np.zeros(2, np.float32))]))
            except Exception:  # noqa: BLE001 — teardown races are OK to error
                pass
            finally:
                done.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        time.sleep(0.005)
        q.stop()
        sink.stop()
        assert done.wait(10), "producer wedged in native ring push"


def test_llm_scheduler_close_mid_generation():
    """Killing the filter while n_parallel streams are mid-decode must
    terminate the scheduler thread and not wedge or throw."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    ZOO = "zoo://gpt?vocab=64&d_model=32&n_heads=4&n_layers=2"
    for _ in range(3):
        fw = find_filter("llm")()
        fw.open(FilterProperties(
            model_files=(ZOO,), invoke_async=True,
            custom_properties="max_tokens:64,n_parallel:2,max_len:128"))
        got = []
        fw.set_async_dispatcher(lambda o, ctx=None: got.append(1))
        fw.invoke_async([np.array([1, 2, 3], np.int32)], ctx="a")
        fw.invoke_async([np.array([4, 5], np.int32)], ctx="b")
        time.sleep(0.2)   # let generation get going
        fw.close()        # mid-stream teardown
        assert fw._sched is None or not fw._sched.is_alive()


def test_concurrent_single_shot_invokes():
    """One SingleShot handle hammered from 8 threads: the backend lock
    must serialize without loss or corruption."""
    from nnstreamer_tpu import SingleShot
    with SingleShot(model="zoo://mlp?in_dim=8&hidden=4&out_dim=2",
                    framework="jax") as s:
        errs = []

        def worker():
            try:
                for _ in range(10):
                    out = s.invoke([np.ones(8, np.float32)])
                    assert np.asarray(out[0]).shape == (2,)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs


def test_concurrent_prefetch_pipelines_share_coalescer():
    """Two pipelines with prefetch-host=true run concurrently: their
    frames interleave on the SHARED fetch coalescer (one fetcher
    thread, batched device_get across both), and every frame must
    resolve to ITS OWN pipeline's data — no cross-talk, no loss."""
    import threading

    import numpy as np

    from nnstreamer_tpu.pipeline.parser import parse_launch

    n = 40
    results = {"a": [], "b": []}
    done = {k: threading.Event() for k in results}

    def launch(tag, fill):
        capsq = ('"other/tensors,format=static,num_tensors=1,'
                 'types=(string)float32,dimensions=(string)16,'
                 'framerate=(fraction)0/1"')
        # scaler custom filter path stays device-side until the sink
        pipe = parse_launch(
            f"tensortestsrc caps={capsq} pattern=ones num-buffers={n} "
            "! queue max-size-buffers=4 "
            "! tensor_transform mode=arithmetic "
            f"option=mul:{fill} "
            "! tensor_filter framework=jax model=zoo://mlp?in_dim=16 "
            "prefetch-host=true ! queue max-size-buffers=8 "
            "! appsink name=out")

        def cb(buf, tag=tag):
            results[tag].append(buf.chunks[0].host().copy())
            if len(results[tag]) == n:
                done[tag].set()

        pipe["out"].connect(cb)
        pipe.start()
        return pipe

    pa = launch("a", 2)
    pb = launch("b", 3)
    assert done["a"].wait(120) and done["b"].wait(120)
    pa.stop()
    pb.stop()
    # determinism: within a pipeline every frame is identical (same
    # input, same params); across pipelines they differ (scaled input)
    for tag in ("a", "b"):
        assert len(results[tag]) == n
        for arr in results[tag][1:]:
            np.testing.assert_array_equal(arr, results[tag][0])
    assert not np.array_equal(results["a"][0], results["b"][0])


def test_serve_fanout_no_loss_no_duplication():
    """8 concurrent clients hammer one tensor_serve_src scheduler
    (ISSUE 1 satellite): every client must receive exactly its own
    frames back — zero lost, zero duplicated, zero cross-routed —
    while the batcher coalesces across all of them."""
    import socket as _socket

    from nnstreamer_tpu import Buffer

    register_custom_easy("serve_stress_id", lambda x: x)
    s = _socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    server = nt.parse_launch(
        f"tensor_serve_src name=src port={port} id=50 buckets=1,2,4,8 "
        "max-wait-ms=2 max-queue=64 "
        "! tensor_filter framework=custom-easy model=serve_stress_id "
        "! tensor_serve_sink id=50")
    server.start()
    time.sleep(0.2)
    capsq = ('"other/tensors,format=static,num_tensors=1,'
             'types=(string)float32,dimensions=(string)4"')
    n_clients, n_frames = 8, 40
    results = {}

    def run_client(tag):
        c = nt.parse_launch(
            f"appsrc name=in caps={capsq} "
            f"! tensor_query_client port={port} timeout=30 "
            "max-request=16 ! appsink name=out")
        c.start()
        # the payload IS the correlation check: client tag + frame seq
        for i in range(n_frames):
            c["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, tag * 1000 + i, np.float32)]))
        deadline = time.monotonic() + 60
        while len(c["out"].buffers) < n_frames \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        results[tag] = [int(b.chunks[0].host()[0]) for b in c["out"].buffers]
        c["in"].end_stream()
        c.stop()

    threads = [threading.Thread(target=run_client, args=(t,))
               for t in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    rep = server["src"].scheduler.report()
    server.stop()
    for tag in range(n_clients):
        want = [tag * 1000 + i for i in range(n_frames)]
        assert results.get(tag) == want, \
            f"client {tag}: lost/dup/cross-routed replies"
    assert rep["completed"] == n_clients * n_frames
    assert rep["shed_admission"] == 0 and rep["shed_deadline"] == 0
    # the point of the scheduler: requests actually shared batches
    assert rep["batches"] < n_clients * n_frames
    assert rep["occupancy_avg"] > 0.0


def test_render_time_adaptive_qos_bounded_under_slow_fetch(monkeypatch):
    """D2H degrades ~100x mid-stream: every fetch is slowed to 0.25 s. The sink's qos=true feedback engages
    the tensor_filter's throttle, frames drop AT THE FILTER (counted in
    qos_dropped — no invoke, no fetch ticket), and the fetch backlog
    stays bounded instead of ballooning one ticket per source frame."""
    import jax

    from nnstreamer_tpu.pipeline.parser import parse_launch
    from nnstreamer_tpu.tensors.transfer import fetch_stats

    real_get = jax.device_get

    def slow_get(tree):
        time.sleep(0.25)  # ~100x a healthy coalesced fetch
        return real_get(tree)

    monkeypatch.setattr(jax, "device_get", slow_get)
    fetch_stats(reset=True)
    n = 60
    capsq = ('"other/tensors,format=static,num_tensors=1,'
             'types=(string)float32,dimensions=(string)64:8,'
             'framerate=(fraction)30/1"')
    pipe = parse_launch(
        f"tensortestsrc caps={capsq} pattern=random is-live=true "
        f"num-buffers={n} ! queue leaky=downstream max-size-buffers=4 "
        "! tensor_filter name=f framework=jax model=zoo://mlp?dtype=float32 "
        "prefetch-host=true ! queue max-size-buffers=4 "
        "! appsink name=out qos=true")
    delivered = []
    pipe["out"].connect(lambda b: delivered.append(b.host_arrays()))
    pipe.start()
    assert pipe.wait_eos(timeout=120)
    stats = dict(pipe["f"].stats)
    pipe.stop()
    s = fetch_stats()
    # the throttle engaged: frames were dropped BEFORE invoke
    assert stats["qos_dropped"] > 5, stats
    # bounded backlog: far fewer fetch tickets than source frames (the
    # unthrottled failure mode files one per frame = 60)
    assert s["frames"] <= 35, s
    assert len(delivered) == s["frames"]
    # every delivered frame still fully materialized (no corruption)
    assert all(a[0].shape == (8, 10) for a in delivered)
