"""Serving stack: dynamic-batching scheduler between client streams and
the filter (ISSUE 1 — tensor_serve).

Covers the batcher invariants (bucketing, max-wait flush, admission and
deadline shed), demux correctness under interleaved streams, the
tensor_serve_src/sink elements end-to-end over the query wire protocol
(including SHED -> upstream QosEvent and client-disconnect slot
reclamation), the bounded-jit-cache guarantee, and the satellites riding
along: the persistent-thread watchdog and reservoir percentiles.
"""
import socket
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.analysis.flow import check_identities
from nnstreamer_tpu.filters import register_custom_easy
from nnstreamer_tpu.serve import BucketBatcher, Request, ServeScheduler, \
    stack_requests
from nnstreamer_tpu.obs.metrics import Reservoir
from nnstreamer_tpu.obs.report import Tracer
from nnstreamer_tpu.utils.watchdog import Watchdog


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _req(stream, value, dim=4, **kw):
    return Request(stream, [np.full(dim, float(value), np.float32)], **kw)


# ---------------------------------------------------------------- batcher

class TestBucketBatcher:
    def test_bucket_for(self):
        b = BucketBatcher(buckets=(1, 2, 4, 8), max_wait_s=0.0)
        assert [b.bucket_for(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
            [1, 2, 4, 4, 8, 8, 8]

    def test_full_bucket_flushes_without_waiting(self):
        b = BucketBatcher(buckets=(1, 2, 4), max_wait_s=10.0, max_queue=8)
        for i in range(4):
            assert b.submit(_req(0, i))
        t0 = time.monotonic()
        batch = b.next_batch()
        assert time.monotonic() - t0 < 1.0  # did NOT sit out max_wait
        assert [r.arrays[0][0] for r in batch] == [0.0, 1.0, 2.0, 3.0]
        assert b.depth() == 0

    def test_lone_request_flushes_at_max_wait(self):
        b = BucketBatcher(buckets=(1, 2, 4), max_wait_s=0.05)
        b.submit(_req(0, 7))
        t0 = time.monotonic()
        batch = b.next_batch()
        waited = time.monotonic() - t0
        assert len(batch) == 1 and batch[0].arrays[0][0] == 7.0
        assert waited < 2.0  # flushed on deadline, not wedged
        assert b.bucket_for(len(batch)) == 1

    def test_admission_shed_at_max_queue(self):
        b = BucketBatcher(buckets=(4,), max_wait_s=10.0, max_queue=2)
        assert b.submit(_req(0, 0))
        assert b.submit(_req(0, 1))
        assert not b.submit(_req(0, 2))  # stream 0's budget exhausted
        assert b.submit(_req(1, 3))      # per-stream: stream 1 unaffected
        assert b.stats["shed_admission"] == 1

    def test_deadline_shed(self):
        b = BucketBatcher(buckets=(2,), max_wait_s=0.2)
        shed = []
        dead = _req(0, 0, deadline=time.monotonic() - 0.01,
                    on_shed=shed.append)
        live = _req(1, 1)
        b.submit(dead)
        b.submit(live)
        batch = b.next_batch()
        assert [r.arrays[0][0] for r in batch] == [1.0]
        assert shed == [dead]
        assert b.stats["shed_deadline"] == 1

    def test_cancel_stream_reclaims_slots(self):
        b = BucketBatcher(buckets=(8,), max_wait_s=10.0, max_queue=4)
        for i in range(3):
            b.submit(_req(0, i))
        b.submit(_req(1, 9))
        assert b.cancel_stream(0) == 3
        assert b.depth() == 1 and b.depth(0) == 0
        assert b.stats["cancelled"] == 3
        # the freed budget is usable again
        assert b.submit(_req(0, 10))

    def test_signature_mismatch_opens_next_batch(self):
        b = BucketBatcher(buckets=(1, 2, 4), max_wait_s=0.0)
        b.submit(_req(0, 0, dim=4))
        b.submit(_req(1, 1, dim=4))
        b.submit(_req(2, 2, dim=8))  # different shape: not stackable
        first = b.next_batch()
        second = b.next_batch()
        assert [r.arrays[0].shape for r in first] == [(4,), (4,)]
        assert [r.arrays[0].shape for r in second] == [(8,)]

    def test_stack_requests_pads_to_bucket(self):
        reqs = [_req(0, 1), _req(1, 2)]
        stacked = stack_requests(reqs, 4)
        assert stacked[0].shape == (4, 4)
        # padding repeats the last real row
        np.testing.assert_array_equal(stacked[0][2], stacked[0][1])
        np.testing.assert_array_equal(stacked[0][3], stacked[0][1])


# -------------------------------------------------------------- scheduler

class TestServeScheduler:
    def test_demux_interleaved_streams(self):
        """Three streams submit interleaved; every stream gets exactly
        its own frames back, doubled, in order — correlation rides the
        Request objects, not arrival order."""
        sched = ServeScheduler(buckets=(1, 2, 4), max_wait_s=0.002,
                               invoke_fn=lambda xs: [x * 2 for x in xs])
        got = {s: [] for s in range(3)}
        done = threading.Event()
        lock = threading.Lock()

        def on_result(req, row):
            with lock:
                got[req.stream_id].append(float(row[0][0]))
                if sum(len(v) for v in got.values()) == 30:
                    done.set()

        sched.start()
        try:
            for i in range(10):
                for s in range(3):
                    assert sched.submit(s, [np.full(4, 100 * s + i,
                                                    np.float32)],
                                        seq=i, on_result=on_result)
            assert done.wait(timeout=20)
        finally:
            sched.stop()
        for s in range(3):
            assert got[s] == [2.0 * (100 * s + i) for i in range(10)]
        rep = sched.report()
        assert rep["completed"] == 30
        assert rep["shed_admission"] == 0 and rep["shed_deadline"] == 0
        assert 0.0 < rep["occupancy_avg"] <= 1.0
        assert rep["queue_delay_us"]["p50"] >= 0.0
        assert rep["batch_latency_us"]["p99"] >= rep["batch_latency_us"]["p50"]

    def test_admission_shed_invokes_on_shed(self):
        sched = ServeScheduler(buckets=(4,), max_wait_s=10.0, max_queue=1)
        shed = []
        assert sched.submit(0, [np.zeros(4, np.float32)])
        assert not sched.submit(0, [np.zeros(4, np.float32)],
                                on_shed=shed.append)
        assert len(shed) == 1

    def test_invoke_failure_sheds_batch_keeps_serving(self):
        calls = {"n": 0}

        def flaky(xs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient")
            return xs

        sched = ServeScheduler(buckets=(1,), max_wait_s=0.001,
                               invoke_fn=flaky)
        shed, ok = threading.Event(), threading.Event()
        sched.start()
        try:
            sched.submit(0, [np.zeros(4, np.float32)],
                         on_shed=lambda r: shed.set())
            assert shed.wait(timeout=10)
            sched.submit(0, [np.zeros(4, np.float32)],
                         on_result=lambda r, row: ok.set())
            assert ok.wait(timeout=10)  # the worker survived the failure
        finally:
            sched.stop()

    def test_result_error_does_not_starve_batch(self):
        """One dead client's callback raising must not stop the demux
        from answering the other rows of the same batch."""
        sched = ServeScheduler(buckets=(2,), max_wait_s=10.0)
        reqs = [Request(0, [np.zeros(4, np.float32)],
                        on_result=lambda r, row: 1 / 0),
                Request(1, [np.ones(4, np.float32)],
                        on_result=lambda r, row: None)]
        for r in reqs:
            sched.batcher.submit(r)
        batch, bucket, stacked = sched.next_batch()
        sched.complete(batch, stacked)
        rep = sched.report()
        assert rep["result_errors"] == 1
        assert rep["completed"] == 2


# ------------------------------------------------- elements (end-to-end)

CAPS4 = ('other/tensors,format=static,num_tensors=1,'
         'types=(string)float32,dimensions=(string)4')


@pytest.fixture(scope="module", autouse=True)
def _serve_models():
    register_custom_easy("serve_double", lambda x: x * 2)
    register_custom_easy("serve_slow",
                         lambda x: (time.sleep(0.05), x)[1])
    yield


def _push_and_wait(client, values, want, timeout=30):
    for v in values:
        client["in"].push_buffer(Buffer.from_arrays(
            [np.full(4, float(v), np.float32)]))
    deadline = time.monotonic() + timeout
    while len(client["out"].buffers) < want and time.monotonic() < deadline:
        time.sleep(0.02)
    return [float(b.chunks[0].host()[0]) for b in client["out"].buffers]


class TestServeElements:
    def test_round_trip_two_clients(self):
        """serve_src ! filter ! serve_sink serves two concurrent query
        clients; each gets exactly its own frames back, doubled."""
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=40 buckets=1,2,4 '
            'max-wait-ms=2 '
            '! tensor_filter framework=custom-easy model=serve_double '
            '! tensor_serve_sink id=40')
        server.start()
        time.sleep(0.2)
        results = {}

        def run_client(tag, base):
            c = parse_launch(
                f'appsrc name=in caps="{CAPS4}" '
                f'! tensor_query_client port={port} timeout=15 '
                'max-request=8 ! appsink name=out')
            c.start()
            results[tag] = _push_and_wait(c, [base + i for i in range(6)], 6)
            c["in"].end_stream()
            c.stop()

        threads = [threading.Thread(target=run_client, args=(t, 100 * t))
                   for t in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=40)
        rep = server["src"].scheduler.report()
        server.stop()
        for tag in (1, 2):
            assert results[tag] == [2.0 * (100 * tag + i) for i in range(6)]
        assert rep["completed"] == 12
        assert rep["batches"] >= 1
        assert rep["queue_delay_us"]["p95"] >= rep["queue_delay_us"]["p50"]

    def test_shed_emits_qos_and_accounts_every_frame(self):
        """A client outrunning the filter is shed with retry-after; the
        client books the shed, raises an upstream QosEvent, and every
        sent frame is accounted exactly once (result xor shed)."""
        from nnstreamer_tpu.pipeline.events import QosEvent
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=41 buckets=1 '
            'max-wait-ms=1 max-queue=2 retry-after-ms=25 '
            '! tensor_filter framework=custom-easy model=serve_slow '
            '! tensor_serve_sink id=41')
        server.start()
        time.sleep(0.2)
        client = parse_launch(
            f'appsrc name=in caps="{CAPS4}" '
            f'! tensor_query_client name=qc port={port} timeout=15 '
            'max-request=64 ! appsink name=out')
        qos = []
        orig = client["in"].handle_upstream_event
        client["in"].handle_upstream_event = \
            lambda pad, ev: (qos.append(ev), orig(pad, ev))
        client.start()
        sent = 24
        for i in range(sent):
            client["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with client["qc"]._plock:
                pending = len(client["qc"]._pending)
            if (len(client["out"].buffers)
                    + client["qc"].stats["shed"] >= sent and not pending):
                break
            time.sleep(0.05)
        n_result = len(client["out"].buffers)
        n_shed = client["qc"].stats["shed"]
        rep = server["src"].scheduler.report()
        client["in"].end_stream()
        client.stop()
        server.stop()
        assert n_shed > 0, "max-queue=2 against a 50ms filter must shed"
        assert n_result + n_shed == sent  # nothing lost, nothing duplicated
        assert rep["shed_admission"] == n_shed
        shed_events = [e for e in qos if isinstance(e, QosEvent)]
        assert shed_events, "SHED must surface as an upstream QosEvent"
        assert shed_events[0].period_ns == 25_000_000  # retry-after echo

    def test_client_disconnect_reclaims_and_recovers(self):
        """A client dying with requests queued must not wedge the
        batcher: its slots are reclaimed and later clients are served."""
        from nnstreamer_tpu.edge.protocol import MsgKind, buffer_to_wire, \
            recv_msg, send_msg
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=42 buckets=1 '
            'max-wait-ms=1 max-queue=16 '
            '! tensor_filter framework=custom-easy model=serve_slow '
            '! tensor_serve_sink id=42')
        server.start()
        time.sleep(0.2)
        # raw-socket client: handshake, burst, die without reading replies
        raw = socket.create_connection(("localhost", port), timeout=5)
        send_msg(raw, MsgKind.CAPS, {"caps": CAPS4})
        recv_msg(raw)
        meta, payloads = buffer_to_wire(
            Buffer.from_arrays([np.zeros(4, np.float32)]))
        for _ in range(6):
            send_msg(raw, MsgKind.DATA, meta, payloads)
        raw.close()
        # a well-behaved client arriving afterwards is served normally
        client = parse_launch(
            f'appsrc name=in caps="{CAPS4}" '
            f'! tensor_query_client port={port} timeout=15 '
            'max-request=8 ! appsink name=out')
        client.start()
        out = _push_and_wait(client, [5.0], 1)
        rep = server["src"].scheduler.report()
        client["in"].end_stream()
        client.stop()
        server.stop()
        assert out == [5.0]
        # every burst frame either completed before the close was seen
        # or was reclaimed — none left queued, nothing wedged
        assert rep["completed"] + rep["cancelled"] >= 6
        assert server["src"].scheduler.batcher.depth() == 0

    def test_mid_stream_death_batch_settles_for_survivors(self):
        """A client killed BETWEEN submit and settle (its request is
        already admitted, possibly co-batched with a survivor's) must
        not abort the batch: the scheduler reclaims what was still
        queued, the reply path books the dead connection instead of
        raising, and every surviving client's frames settle."""
        from nnstreamer_tpu.edge.protocol import MsgKind, buffer_to_wire, \
            recv_msg, send_msg
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=44 buckets=1,2 '
            'max-wait-ms=20 max-queue=16 '
            '! tensor_filter framework=custom-easy model=serve_slow '
            '! tensor_serve_sink id=44')
        server.start()
        time.sleep(0.2)
        # victim: raw socket, handshake + burst, then dies mid-flight —
        # after the submits are admitted but before any result lands
        raw = socket.create_connection(("localhost", port), timeout=5)
        send_msg(raw, MsgKind.CAPS, {"caps": CAPS4})
        recv_msg(raw)
        meta, payloads = buffer_to_wire(
            Buffer.from_arrays([np.full(4, 9.0, np.float32)]))
        # survivor submits concurrently so some batches mix both streams
        client = parse_launch(
            f'appsrc name=in caps="{CAPS4}" '
            f'! tensor_query_client port={port} timeout=15 '
            'max-request=16 ! appsink name=out')
        client.start()
        for i in range(8):
            send_msg(raw, MsgKind.DATA, meta, payloads)
            client["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        raw.close()  # die between submit and settle
        out = _push_and_wait(client, [], 8)
        rep = server["src"].scheduler.report()
        depth = server["src"].scheduler.batcher.depth()
        client["in"].end_stream()
        client.stop()
        server.stop()
        assert sorted(out) == [float(i) for i in range(8)]  # survivors whole
        # the victim's 8 frames are fully accounted: completed before
        # the close was noticed, or reclaimed from the queue
        assert rep["completed"] + rep["cancelled"] + rep["shed_admission"] \
            >= 16
        assert depth == 0  # nothing left wedged in the batcher

    def test_jit_cache_bounded_by_buckets(self):
        """The acceptance bound: across ragged concurrency the jax jit
        cache holds at most len(buckets) compiled signatures, because
        every batch is padded up to a bucket size."""
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=43 buckets=1,2,4 '
            'max-wait-ms=4 '
            '! tensor_filter name=f framework=jax '
            'model="zoo://mlp?in_dim=4&hidden=8&out_dim=4" '
            '! tensor_serve_sink id=43')
        server.start()
        time.sleep(0.2)

        def run_client(tag, n):
            c = parse_launch(
                f'appsrc name=in caps="{CAPS4}" '
                f'! tensor_query_client port={port} timeout=60 '
                'max-request=8 ! appsink name=out')
            c.start()
            _push_and_wait(c, range(n), n, timeout=60)
            got = len(c["out"].buffers)
            c["in"].end_stream()
            c.stop()
            assert got == n

        threads = [threading.Thread(target=run_client, args=(t, 8))
                   for t in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
        n_sigs = len(server["f"].fw._jit_cache)
        rep = server["src"].scheduler.report()
        server.stop()
        assert rep["completed"] == 24
        assert 1 <= n_sigs <= 3, \
            f"jit cache must stay within buckets, saw {n_sigs} signatures"


# ------------------------------------------- tentpole: graceful drain

class TestDrainSettlement:
    def test_drain_settles_pending_correlations(self):
        """Pipeline.drain() on the serving side answers every admitted
        request — RESULT or SHED, never silence — before close: the
        client's correlation table empties, the accounting balances
        exactly, and the scheduler queue is dry."""
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=44 buckets=1,2,4 '
            'max-wait-ms=2 retry-after-ms=10 '
            '! tensor_filter framework=custom-easy model=serve_slow '
            '! tensor_serve_sink id=44')
        server.start()
        time.sleep(0.2)
        client = parse_launch(
            f'appsrc name=in caps="{CAPS4}" '
            f'! tensor_query_client name=qc port={port} timeout=15 '
            'max-request=32 ! appsink name=out')
        client.start()
        sent = 12
        for i in range(sent):
            client["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        # let the requests genuinely be in flight before pulling the plug:
        # every one handed to the wire (entry[2] is the connection it
        # went out on, -1 before). One the client's chain thread has yet
        # to send when the server stops meets a closed socket, not a
        # drain, and on a loaded host that thread can be eleven behind
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with client["qc"]._plock:
                entries = list(client["qc"]._pending)
            if (len(entries) + len(client["out"].buffers)
                    + client["qc"].stats["shed"] >= sent
                    and all(e[2] != -1 for e in entries)):
                break
            time.sleep(0.005)
        ok = server.drain(deadline=30)
        # every correlation must have settled BEFORE the server closed:
        # no waiting on reconnect/replay here, just reading what arrived
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with client["qc"]._plock:
                pending = len(client["qc"]._pending)
            if (len(client["out"].buffers)
                    + client["qc"].stats["shed"] >= sent and not pending):
                break
            time.sleep(0.02)
        n_result = len(client["out"].buffers)
        n_shed = client["qc"].stats["shed"]
        with client["qc"]._plock:
            pending = len(client["qc"]._pending)
        rep = server["src"].scheduler.report()
        client["in"].end_stream()
        client.stop()
        assert ok is True, "drain must flush inside the deadline"
        assert pending == 0, "drain left correlations unsettled"
        assert n_result + n_shed == sent  # RESULT xor SHED, nothing lost
        assert n_result > 0, "everything shed: nothing was in flight"
        assert server["src"].scheduler.pending() == 0
        assert rep["completed"] == n_result
        # the declared conservation identity replaces hand-written
        # counter math: every admitted request reached exactly one
        # terminal (raises AssertionError with a breakdown otherwise)
        check_identities({**rep, "pending": 0},
                         names=["serve-settlement"])
        vals = [float(b.chunks[0].host()[0]) for b in client["out"].buffers]
        assert vals == sorted(vals)  # per-stream order survives the drain
        assert set(vals) <= {float(i) for i in range(sent)}  # serve_slow: id

    def test_drain_idle_pipeline_is_clean(self):
        """Draining a serving pipeline with nothing in flight reaches
        EOS promptly and twice in a row is safe."""
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=45 buckets=1 '
            'max-wait-ms=1 '
            '! tensor_filter framework=custom-easy model=serve_double '
            '! tensor_serve_sink id=45')
        server.start()
        time.sleep(0.1)
        assert server.drain(deadline=10) is True
        assert server.drain(deadline=1) is True  # idempotent
        assert server["src"].scheduler.pending() == 0


# ------------------------------------------------- ROI frame settlement

class TestRoiSettlement:
    """The ROI gate's whole-frame settlement: one terminal per frame
    (RESULT xor SHED), and a shed frame's still-queued sibling crops
    are cancelled, not left to burn TPU batches (found by
    `make flowcheck`: the roi-settlement identity could not balance)."""

    def _element(self, max_queue=16):
        from nnstreamer_tpu.serve.elements import TensorServeSrc
        el = TensorServeSrc("roi-src")
        el.scheduler = ServeScheduler(buckets=(4,), max_wait_s=10.0,
                                      max_queue=max_queue)
        sent = []
        el._send = lambda cid, kind, meta, payloads=(): \
            sent.append((kind.name, meta))
        return el, sent

    def _crops_buf(self, n=4):
        return Buffer.from_arrays(
            [np.arange(n * 8 * 8 * 3, dtype=np.float32)
             .reshape(n, 8, 8, 3)], pts=123)

    def test_admission_shed_cancels_sibling_crops(self):
        """Crop 3 of 4 sheds at admission: the frame settles as ONE
        SHED, the two already-queued siblings are reclaimed, and the
        scheduler's own settlement identity balances."""
        el, sent = self._element(max_queue=2)
        el._admit_roi(7, self._crops_buf(4), seq=0, roi={"tile": 8})
        s = el.stats.snapshot()
        assert s["serve_roi_requests"] == 1 and s["serve_roi_crops"] == 4
        assert s["serve_roi_shed"] == 1 and s["serve_roi_results"] == 0
        assert [k for k, _ in sent] == ["SHED"]
        assert sent[0][1]["retry_after_ms"] > 0
        # the shed frame's queued siblings were cancelled, not stranded
        assert el.scheduler.batcher.depth() == 0
        assert el.scheduler.batcher.stats["cancelled"] == 2
        check_identities({**el.scheduler.report(), "pending": 0},
                         names=["serve-settlement"])
        check_identities({**s, "serve_roi_pending": 0},
                         names=["roi-settlement"])

    def test_complete_frame_settles_as_one_result(self):
        el, sent = self._element()
        el._admit_roi(7, self._crops_buf(4), seq=0, roi={"tile": 8})
        batch, _bucket, stacked = el.scheduler.next_batch()
        assert len(batch) == 4
        el.scheduler.complete(batch, stacked)
        s = el.stats.snapshot()
        assert s["serve_roi_results"] == 1 and s["serve_roi_shed"] == 0
        assert [k for k, _ in sent] == ["RESULT"]
        check_identities({**s, "serve_roi_pending": 0},
                         names=["roi-settlement"])


# ------------------------------------------------------ satellite: watchdog

class TestWatchdog:
    def test_single_persistent_thread(self):
        """feed() must not churn threads: many feeds, one watcher."""
        fired = threading.Event()
        wd = Watchdog(0.2, fired.set)
        try:
            before = threading.active_count()
            for _ in range(200):
                wd.feed()
            assert threading.active_count() <= before + 1
            watchers = [t for t in threading.enumerate()
                        if t.name == "watchdog"]
            assert len(watchers) == 1
        finally:
            wd.destroy()

    def test_feed_postpones_and_fires_once(self):
        fires = []
        wd = Watchdog(0.15, lambda: fires.append(time.monotonic()))
        try:
            t0 = time.monotonic()
            wd.feed()
            time.sleep(0.08)
            wd.feed()          # pushes the deadline out past t0 + 0.15
            time.sleep(0.3)
            assert len(fires) == 1
            assert fires[0] - t0 >= 0.15
            time.sleep(0.2)    # disarmed after firing: no re-fire
            assert len(fires) == 1
        finally:
            wd.destroy()

    def test_destroy_suppresses_pending_fire(self):
        fired = threading.Event()
        wd = Watchdog(0.1, fired.set)
        wd.feed()
        wd.destroy()
        time.sleep(0.25)
        assert not fired.is_set()

    def test_quiesce_suppresses_fire_resume_rearms_fresh(self):
        """A deliberate stall (drain flush) must not read as a hang:
        quiesce() holds the dog past its deadline, and resume() grants
        a fresh full timeout instead of firing retroactively."""
        fires = []
        wd = Watchdog(0.1, lambda: fires.append(time.monotonic()))
        try:
            wd.feed()
            wd.quiesce()
            time.sleep(0.3)          # deadline lapses while quiesced
            assert fires == []       # the drain never looked like a stall
            t0 = time.monotonic()
            wd.resume()
            time.sleep(0.04)
            assert fires == []       # fresh timeout, not a retroactive bite
            deadline = time.monotonic() + 5
            while not fires and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(fires) == 1 and fires[0] - t0 >= 0.1
        finally:
            wd.destroy()

    def test_quiesce_nests(self):
        """Overlapping drains stack: the dog only wakes when every
        quiesce has been balanced by a resume."""
        fired = threading.Event()
        wd = Watchdog(0.08, fired.set)
        try:
            wd.feed()
            wd.quiesce()
            wd.quiesce()
            wd.resume()
            assert wd.quiesced       # one resume is not enough
            time.sleep(0.2)
            assert not fired.is_set()
            wd.resume()
            assert not wd.quiesced
            assert fired.wait(2.0)   # now the lapsed-deadline clock runs
        finally:
            wd.destroy()


# --------------------------------------------- satellite: trace percentiles

class TestPercentiles:
    def test_reservoir_exact_under_capacity(self):
        r = Reservoir(k=512)
        for v in range(101):
            r.add(float(v))
        p = r.percentiles()
        assert p["p50"] == 50.0 and p["p95"] == 95.0 and p["p99"] == 99.0

    def test_reservoir_bounded_memory(self):
        r = Reservoir(k=64)
        for v in range(10_000):
            r.add(float(v))
        assert len(r.samples) == 64 and r.n == 10_000
        # still representative: p50 within the middle half of the stream
        assert 2_000 < r.percentiles()["p50"] < 8_000

    def test_reservoir_deterministic(self):
        a, b = Reservoir(k=8), Reservoir(k=8)
        for v in range(1000):
            a.add(float(v))
            b.add(float(v))
        assert a.samples == b.samples

    def test_window_reservoir_forgets_old_pressure(self):
        # deterministic clock via explicit `now`: burst-era samples must
        # fall out of the window, or an autoscaler reading p95 as its
        # control signal would never see recovery (and never scale down)
        from nnstreamer_tpu.obs.metrics import WindowReservoir
        r = WindowReservoir(window_s=2.0)
        for i in range(50):
            r.add(300_000.0, now=10.0 + i * 0.01)  # 300ms burst delays
        assert r.percentiles(qs=(95,), now=10.5)["p95"] == 300_000.0
        for i in range(20):
            r.add(500.0, now=13.0 + i * 0.01)      # quiet again
        p = r.percentiles(qs=(50, 95), now=13.2)
        assert p["p95"] == 500.0 and p["p50"] == 500.0
        assert r.n == 70  # lifetime count survives the pruning

    def test_window_reservoir_bounded_and_empty_window(self):
        from nnstreamer_tpu.obs.metrics import WindowReservoir
        r = WindowReservoir(window_s=60.0, k=16)
        for i in range(1000):
            r.add(float(i), now=100.0 + i * 1e-4)
        assert len(r._buf) <= 17  # k newest (+1 transient before prune)
        r2 = WindowReservoir(window_s=1.0)
        r2.add(42.0, now=5.0)
        r2.add(43.0, now=99.0)  # first sample long expired
        assert r2.percentiles(qs=(95,), now=99.0)["p95"] == 43.0

    def test_tracer_report_has_percentile_columns(self):
        tr = Tracer()
        for v in (1, 2, 3, 4, 100):
            tr.observe("serve:queue_delay", v * 1e3)  # ns
        rep = tr.report()["serve:queue_delay"]
        assert rep["buffers"] == 5
        assert rep["interlatency_us_p50"] == pytest.approx(3.0)
        assert rep["interlatency_us_p99"] == pytest.approx(100.0)
        assert rep["interlatency_us_max"] == pytest.approx(100.0)


# ------------------------------------------------- runtime lock validator

class TestRuntimeLockValidator:
    def test_serve_path_matches_static_graph(self):
        """Drive the scheduler's real worker threads under instrumented
        locks and cross-check the RECORDED acquisition graph against
        racecheck's static lock-order graph: the run must witness no
        deadlockable order (acyclic) and no edge the static pass missed."""
        from pathlib import Path

        import nnstreamer_tpu
        from nnstreamer_tpu.analysis.concurrency import (
            LockMonitor, analyze_paths, instrument_counters,
            instrument_object)

        mon = LockMonitor()
        sched = ServeScheduler(buckets=(1, 2, 4), max_wait_s=0.002,
                               invoke_fn=lambda xs: [x * 2 for x in xs])
        instrument_object(sched, mon)            # ServeScheduler._mlock
        instrument_object(sched.batcher, mon)    # BucketBatcher._cond
        instrument_counters(sched.stats, mon)
        instrument_counters(sched.batcher.stats, mon)

        done = threading.Event()
        results = []
        rlock = threading.Lock()

        def on_result(req, row):
            with rlock:
                results.append(req.stream_id)
                if len(results) == 30:
                    done.set()

        sched.start()
        try:
            for i in range(10):
                for s in range(3):
                    assert sched.submit(s, [np.full(4, float(i),
                                                    np.float32)],
                                        seq=i, on_result=on_result)
            assert done.wait(timeout=20)
        finally:
            sched.stop()

        assert mon.acquisitions, "instrumented locks were never taken"
        pkg = Path(nnstreamer_tpu.__file__).parent
        static = analyze_paths([str(pkg)]).lock_edges
        cycles, missed = mon.check_against_static(static)
        assert cycles == [], f"runtime witnessed a deadlockable order: {cycles}"
        assert missed == set(), f"static graph missed edges: {missed}"
        # the serve path's canonical nestings were actually exercised
        assert ("ServeScheduler._mlock", "Counters._lock") in mon.edge_set()
        assert ("BucketBatcher._cond", "Counters._lock") in mon.edge_set()


# ------------------------------------------------- sharded serving (mesh)

CAPS64 = ('other/tensors,format=static,num_tensors=1,'
          'types=(string)float32,dimensions=(string)64')


class TestMeshServe:
    def test_bucket_snapping_to_dp_multiple(self):
        """A mesh-aware batcher snaps every bucket up to a multiple of
        the data-parallel degree, so every stacked batch divides the
        mesh; padded rows are accounted exactly as before."""
        b = BucketBatcher(buckets=(1, 2, 4, 8), max_wait_s=0.0,
                          snap_multiple=4)
        assert b.buckets == [4, 8]
        assert BucketBatcher(buckets=(1, 2, 4, 8),
                             max_wait_s=0.0).buckets == [1, 2, 4, 8]
        # 3 requests land in the snapped 4-bucket: 1 padded row, padded
        # by repeating the last request's rows (as today)
        for i in range(3):
            b.submit(_req(0, i))
        batch = b.next_batch()
        bucket = b.bucket_for(len(batch))
        assert bucket == 4
        stacked = stack_requests(batch, bucket)
        assert stacked[0].shape == (4, 4)
        assert np.array_equal(stacked[0][3], stacked[0][2])

    def test_scheduler_places_batches_on_mesh(self):
        """With ``mesh_spec`` the scheduler snaps its buckets by dp and
        lays every stacked batch out across the mesh before the filter
        sees it."""
        import jax
        sched = ServeScheduler(buckets=(1, 2, 4, 8), max_wait_s=0.01,
                               mesh_spec="8x1x1", name="ms")
        assert sched.batcher.buckets == [8]
        for i in range(8):
            assert sched.submit(0, [np.full(4, float(i), np.float32)])
        batch, bucket, stacked = sched.next_batch()
        assert bucket == 8 and len(batch) == 8
        assert isinstance(stacked[0], jax.Array)
        assert stacked[0].shape == (8, 4)
        assert len(stacked[0].sharding.device_set) == 8
        rep = sched.report()
        assert rep["mesh"] == "8x1x1"
        assert rep["buckets"] == [8]
        assert rep["devices"] == 8
        assert rep["placed_batches"] == 1

    def test_scheduler_refuses_unavailable_mesh(self):
        """A spec the host cannot satisfy is an error at the first
        batch — never one chip quietly serving under a mesh's name."""
        sched = ServeScheduler(buckets=(1, 2, 4, 8), max_wait_s=0.01,
                               mesh_spec="64x1x1", name="ms-refuse")
        assert sched.batcher.buckets == [64]
        for i in range(4):
            assert sched.submit(0, [np.full(4, float(i), np.float32)])
        with pytest.raises(ValueError, match="needs 64 devices"):
            sched.next_batch()
        assert sched.report()["placed_batches"] == 0

    def test_mesh_serve_end_to_end_zero_loss(self):
        """The serve chaos accounting identity with the mesh path
        active: a client racing a mesh-serving pipeline gets every
        frame accounted exactly once (result xor shed), and the
        scheduler's report shows the sharded path actually ran."""
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=44 '
            'buckets=1,2,4,8 mesh=8x1x1 max-wait-ms=2 max-queue=2 '
            'retry-after-ms=10 '
            '! tensor_filter framework=jax model=zoo://mlp?dtype=float32 '
            'custom=mesh:8x1x1 ! tensor_serve_sink id=44')
        server.start()
        time.sleep(0.2)
        client = parse_launch(
            f'appsrc name=in caps="{CAPS64}" '
            f'! tensor_query_client name=qc port={port} timeout=15 '
            'max-request=64 ! appsink name=out')
        client.start()
        sent = 24
        for i in range(sent):
            client["in"].push_buffer(Buffer.from_arrays(
                [np.full(64, float(i), np.float32)]))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with client["qc"]._plock:
                pending = len(client["qc"]._pending)
            if (len(client["out"].buffers)
                    + client["qc"].stats["shed"] >= sent and not pending):
                break
            time.sleep(0.05)
        n_result = len(client["out"].buffers)
        n_shed = client["qc"].stats["shed"]
        rep = server["src"].scheduler.report()
        client["in"].end_stream()
        client.stop()
        server.stop()
        assert n_result > 0, "mesh serve path returned nothing"
        assert n_result + n_shed == sent  # nothing lost, nothing duplicated
        assert rep["shed_admission"] == n_shed
        assert rep["mesh"] == "8x1x1"
        assert rep["buckets"] == [8]  # 1,2,4,8 snapped to dp=8
        assert rep["devices"] == 8
        assert rep["placed_batches"] >= 1
        # every result row is the mlp's 10-class output
        assert all(b.chunks[0].host().shape[-1] == 10
                   for b in client["out"].buffers)
