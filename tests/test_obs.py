"""Frame-level observability (ISSUE 12): trace contexts, span rings,
the flight recorder, the metrics plane, and the wire trace field.

Covers the unit layer (TraceContext stamp/child/pickle, wire
encode/decode with malformed-peer safety, ring recording and
snapshotting), the pipeline layer (a frame's span tree is connected —
source root, queue wait, element hops — and settles the end-to-end
histogram with queue/compute/wire attribution), the wire layer (the
trace field is strictly opt-in per link: un-negotiated traffic is
byte-identical; negotiated DATA_BATCH headers version to fhdr=2 and
re-link the remote tree), the telemetry plane (render/parse round-trip,
the scrape server's routes, broker registration, the top CLI's table),
and the report-shape regression the transfer/fusion `devices` key is
pinned by.

The cross-process acceptance (router -> replica -> mesh-sharded fused
segment -> response as ONE connected span tree across >=3 pids of valid
Chrome trace_event JSON) lives at the bottom, with the slow full-mesh
arm marked `slow`.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.edge import wire
from nnstreamer_tpu.obs import context as obs_ctx
from nnstreamer_tpu.obs import events as obs_events
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import spans as obs_spans
from nnstreamer_tpu.obs import top as obs_top
from nnstreamer_tpu.obs.recorder import RECORDER
from nnstreamer_tpu.obs.server import MetricsServer, scrape

REPO = str(Path(__file__).resolve().parent.parent)

CAPS4 = ('other/tensors,format=static,num_tensors=1,'
         'types=(string)float32,dimensions=(string)4,'
         'framerate=(fraction)0/1')
CAPS64 = ('other/tensors,format=static,num_tensors=1,'
          'types=(string)float32,dimensions=(string)64')


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spans_by_trace(trace_ids):
    """Live-ring spans grouped by trace id (only the asked-for traces,
    so concurrent test history can't bleed in)."""
    want = set(trace_ids)
    out = {t: [] for t in want}
    for _tid, s in obs_spans.snapshot():
        if s[4] in want:
            out[s[4]].append(s)
    return out


def _assert_tree(spans):
    """One connected span tree: exactly one root, no orphan parents."""
    ids = {s[5] for s in spans}
    roots = [s for s in spans if s[6] == 0]
    assert len(roots) == 1, f"want one root, got {roots}"
    for s in spans:
        assert s[6] == 0 or s[6] in ids, f"orphan span {s}"


# ------------------------------------------------------------- context

class TestTraceContext:
    def test_stamp_attaches_and_sets_thread_inheritance(self):
        buf = Buffer.from_arrays([np.zeros(4, np.float32)])
        ctx = obs_ctx.stamp(buf)
        assert obs_ctx.ctx_of(buf) is ctx
        # a fresh (meta-stripped) buffer on the same thread inherits it
        fresh = Buffer.from_arrays([np.zeros(4, np.float32)])
        assert obs_ctx.ensure_ctx(fresh) is ctx
        assert obs_ctx.ctx_of(fresh) is ctx

    def test_ids_are_unique_and_nonzero(self):
        ids = {obs_ctx.next_id() for _ in range(1000)}
        assert len(ids) == 1000
        assert 0 not in ids

    def test_child_forks_accumulators_not_identity(self):
        ctx = obs_ctx.TraceContext(7, 9, 1000, q_ns=5, c_ns=6, w_ns=7)
        kid = ctx.child()
        assert (kid.trace_id, kid.span_id, kid.t0_ns) == (7, 9, 1000)
        assert (kid.q_ns, kid.c_ns, kid.w_ns) == (0, 0, 0)

    def test_pickle_round_trip(self):
        import pickle
        ctx = obs_ctx.TraceContext(7, 9, 1000, q_ns=5, c_ns=6, w_ns=8)
        back = pickle.loads(pickle.dumps(ctx))
        assert (back.trace_id, back.span_id, back.t0_ns,
                back.q_ns, back.c_ns, back.w_ns) == (7, 9, 1000, 5, 6, 8)

    def test_wire_round_trip_preserves_attribution(self):
        ctx = obs_ctx.TraceContext(0xabc, 0xdef, 1234,
                                   q_ns=10, c_ns=20, w_ns=30)
        field = obs_ctx.to_wire(ctx)
        got = obs_ctx.from_wire(field)
        assert got is not None
        back, t_send = got
        assert back.trace_id == 0xabc and back.span_id == 0xdef
        assert back.t0_ns == 1234
        assert (back.q_ns, back.c_ns, back.w_ns) == (10, 20, 30)
        assert t_send == field[2]

    @pytest.mark.parametrize("bad", [
        None, "junk", [], [1, 2], [1, 2, 3, 4, 5, 6, "x"],
        [0, 1, 2, 3, 4, 5, 6],                 # trace_id 0 = untraced
        {"trace": 1},
    ])
    def test_malformed_wire_field_is_dropped_not_fatal(self, bad):
        assert obs_ctx.from_wire(bad) is None


# --------------------------------------------------------------- spans

class TestSpanRings:
    def test_record_span_advances_context_chain(self):
        ctx = obs_ctx.TraceContext(obs_ctx.next_id(), 0, time.time_ns())
        a = obs_spans.record_span("a", "element", time.time_ns(), 10, ctx)
        b = obs_spans.record_span("b", "element", time.time_ns(), 10, ctx)
        assert ctx.span_id == b
        spans = _spans_by_trace([ctx.trace_id])[ctx.trace_id]
        by_id = {s[5]: s for s in spans}
        assert by_id[a][6] == 0                  # first parents the root
        assert by_id[b][6] == a                  # linear causality chain

    def test_record_root_then_children_never_dangle(self):
        buf = Buffer.from_arrays([np.zeros(4, np.float32)])
        ctx = obs_ctx.stamp(buf)
        obs_spans.record_root("src", ctx)
        obs_spans.record_span("hop", "element", time.time_ns(), 5, ctx)
        _assert_tree(_spans_by_trace([ctx.trace_id])[ctx.trace_id])

    def test_disabled_records_nothing_and_returns_zero(self):
        ctx = obs_ctx.TraceContext(obs_ctx.next_id(), 0, time.time_ns())
        obs_spans.set_enabled(False)
        try:
            assert obs_spans.record_span(
                "x", "element", time.time_ns(), 1, ctx) == 0
            assert obs_spans.record_root("x", ctx) == 0
        finally:
            obs_spans.set_enabled(True)
        assert _spans_by_trace([ctx.trace_id])[ctx.trace_id] == []

    def test_ring_is_bounded(self):
        ctx = obs_ctx.TraceContext(obs_ctx.next_id(), 0, time.time_ns())
        for _ in range(obs_spans.RING_SPANS + 100):
            obs_spans.record_span("x", "element", 0, 1, ctx)
        mine = _spans_by_trace([ctx.trace_id])[ctx.trace_id]
        assert len(mine) <= obs_spans.RING_SPANS

    def test_snapshot_names_threads(self):
        seen = {}

        def work():
            obs_spans.record_span("t", "element", time.time_ns(), 1)
            seen["tid"] = threading.get_ident()

        t = threading.Thread(target=work, name="obs-test-thread")
        t.start()
        t.join()
        assert obs_spans.thread_names().get(seen["tid"]) \
            == "obs-test-thread"


class TestPipelineSpans:
    def test_frame_tree_is_connected_and_settles_e2e(self):
        obs_metrics.reset()
        p = parse_launch(
            f'tensortestsrc name=src caps="{CAPS4}" num-buffers=6 '
            '! queue name=q max-size-buffers=4 '
            '! tensor_transform name=tr mode=arithmetic option=add:1 '
            '! appsink name=out')
        p.fuse = False
        p.run(timeout=60)
        bufs = p["out"].buffers
        assert len(bufs) == 6
        traces = [obs_ctx.ctx_of(b).trace_id for b in bufs]
        assert len(set(traces)) == 6             # one trace per frame
        grouped = _spans_by_trace(traces)
        for tid in traces:
            spans = grouped[tid]
            _assert_tree(spans)
            names = {s[0] for s in spans}
            assert {"src", "q", "tr", "out"} <= names
            cats = {s[1] for s in spans}
            assert {"source", "queue", "element"} <= cats
        # the terminal sink fed the e2e histogram with attribution
        samples = obs_metrics.parse(obs_metrics.render())
        count = sum(v for (n, lab), v in samples.items()
                    if n == "nns_e2e_latency_seconds_count"
                    and dict(lab).get("sink") == "out")
        assert count == 6
        qsum = sum(v for (n, lab), v in samples.items()
                   if n == "nns_e2e_queue_seconds_total"
                   and dict(lab).get("sink") == "out")
        assert qsum >= 0.0

    def test_strips_meta_element_inherits_chain_thread_context(self):
        # tensor_aggregator mints fresh output buffers (STRIPS_META):
        # its downstream spans must still join the frame tree via
        # same-thread inheritance instead of detaching
        p = parse_launch(
            f'tensortestsrc name=src caps="{CAPS4}" num-buffers=4 '
            '! tensor_aggregator name=agg frames-out=2 '
            '! appsink name=out')
        p.fuse = False
        p.run(timeout=60)
        bufs = p["out"].buffers
        assert len(bufs) == 2
        for b in bufs:
            ctx = obs_ctx.ctx_of(b)
            assert ctx is not None
            spans = _spans_by_trace([ctx.trace_id])[ctx.trace_id]
            _assert_tree(spans)
            assert {"agg", "out"} <= {s[0] for s in spans}


    def test_utils_imports_nothing_above_itself(self):
        """The import graph's floor: ``utils/`` is what every package
        may import, so it imports none of them (the per-pipeline report
        that condensed the wire, session, fusion and transfer counters
        lives in ``obs/report.py``, not in ``utils/``)."""
        import ast
        root = Path(obs_spans.__file__).resolve().parents[1]
        above = {"edge", "tensors", "serve", "fusion", "filters", "obs"}
        found = []
        for path in sorted((root / "utils").glob("*.py")):
            for node in ast.walk(ast.parse(path.read_text())):
                if isinstance(node, ast.ImportFrom):
                    parts = (node.module or "").split(".")
                    if node.level == 0 and parts[0] == "nnstreamer_tpu":
                        parts = parts[1:]
                    elif node.level != 2:
                        continue     # level 1: a sibling inside utils/
                    names = parts[:1] or [a.name for a in node.names]
                elif isinstance(node, ast.Import):
                    names = [a.name.split(".")[1] for a in node.names
                             if a.name.startswith("nnstreamer_tpu.")]
                else:
                    continue
                found += [(path.name, n) for n in names if n in above]
        assert found == []


# ------------------------------------------------------ flight recorder

class TestFlightRecorder:
    def test_events_emit_counts_and_window(self):
        RECORDER.clear()
        obs_events.emit("breaker", source="f0", state="open")
        obs_events.emit("shed", source="srv", reason="deadline")
        obs_events.emit("shed", source="srv", reason="admission")
        counts = RECORDER.event_counts()
        assert counts == {"breaker": 1, "shed": 2}
        evs = RECORDER.events(window_s=60)
        assert [(e[1], e[2]) for e in evs] == \
            [("breaker", "f0"), ("shed", "srv"), ("shed", "srv")]
        assert evs[0][3] == {"state": "open"}
        RECORDER.clear()
        assert RECORDER.event_counts() == {}

    def test_emit_can_post_bus_message(self):
        p = parse_launch(
            f'tensortestsrc caps="{CAPS4}" num-buffers=1 '
            '! appsink name=out')
        p.run(timeout=30)
        p.bus.drain()
        obs_events.emit("drain", element=p["out"], bus="drain", left=3)
        msgs = [(m.kind, m.data) for m in p.bus.drain()]
        assert ("drain", {"source": "out", "left": 3}) in msgs

    def test_dump_is_valid_chrome_trace(self, tmp_path):
        RECORDER.clear()
        buf = Buffer.from_arrays([np.zeros(4, np.float32)])
        ctx = obs_ctx.stamp(buf)
        obs_spans.record_root("src", ctx)
        obs_spans.record_span("hop", "element", time.time_ns(), 7, ctx)
        obs_events.emit("preempt", source="pipe", grace_s=1.0)
        path = tmp_path / "flight.json"
        doc = RECORDER.dump(str(path))
        with open(path) as f:
            assert json.load(f) == doc           # file == returned doc
        evs = doc["traceEvents"]
        assert all(e["ph"] in ("M", "X", "i") for e in evs)
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in evs)
        mine = [e for e in evs if e["ph"] == "X"
                and e["args"]["trace"] == f"{ctx.trace_id:x}"]
        assert {e["name"] for e in mine} == {"src", "hop"}
        ids = {e["args"]["span"] for e in mine}
        for e in mine:                           # re-linkable tree
            assert e["args"]["parent"] == "0" or \
                e["args"]["parent"] in ids
        inst = [e for e in evs if e["ph"] == "i"]
        assert any(e["name"] == "preempt" for e in inst)

    def test_abort_dump_is_rate_limited_but_preempt_forces(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("NNS_TPU_FLIGHT_DIR", str(tmp_path))
        RECORDER._last_abort_dump = 0.0
        first = RECORDER.dump_abort("crash")
        assert first is not None and os.path.exists(first)
        assert RECORDER.dump_abort("crash") is None     # limited
        forced = RECORDER.dump_abort("preempt", force=True)
        assert forced is not None and forced != first
        RECORDER._last_abort_dump = 0.0

    def test_empty_flight_dir_disables_auto_dumps(self, monkeypatch):
        monkeypatch.setenv("NNS_TPU_FLIGHT_DIR", "")
        RECORDER._last_abort_dump = 0.0
        assert RECORDER.dump_abort("crash", force=True) is None

    def test_pipeline_abort_triggers_black_box_dump(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("NNS_TPU_FLIGHT_DIR", str(tmp_path))
        RECORDER.clear()
        RECORDER._last_abort_dump = 0.0
        p = parse_launch(
            f'tensortestsrc caps="{CAPS4}" num-buffers=4 '
            '! tensor_fault mode=raise every=2 ! appsink name=out')
        p.start()
        deadline = time.monotonic() + 15
        while p._error is None and time.monotonic() < deadline:
            time.sleep(0.02)
        p.stop()
        assert p._error is not None
        dumps = list(tmp_path.glob("flight-*.json"))
        assert len(dumps) == 1
        assert RECORDER.event_counts().get("abort", 0) >= 1
        RECORDER._last_abort_dump = 0.0


# ------------------------------------------------------ metrics plane

class TestMetrics:
    def test_render_parse_round_trip_with_hostile_labels(self):
        text = ('nns_test_metric{pipeline="a\\"b\\\\c"} 4.5\n'
                'nns_other 2\n# a comment\nbroken line\n')
        samples = obs_metrics.parse(text)
        assert samples[("nns_test_metric",
                        (("pipeline", 'a"b\\c'),))] == 4.5
        assert samples[("nns_other", ())] == 2.0

    def test_render_covers_all_sections(self):
        obs_metrics.reset()
        RECORDER.clear()
        obs_events.emit("failover", source="rt")
        p = parse_launch(
            f'tensortestsrc name=src caps="{CAPS4}" num-buffers=3 '
            '! appsink name=out')
        tracer = p.enable_tracing()
        p.fuse = False
        p.start()
        try:
            deadline = time.monotonic() + 30
            while len(p["out"].buffers) < 3 and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            # scrape while the pipeline is still registered (stop()
            # unregisters it from the exposition)
            text = obs_metrics.render()
            samples = obs_metrics.parse(text)
            names = {n for (n, _lab) in samples}
            assert "nns_e2e_latency_seconds_bucket" in names
            assert "nns_e2e_latency_seconds_count" in names
            assert "nns_e2e_queue_seconds_total" in names
            assert "nns_e2e_compute_seconds_total" in names
            assert "nns_e2e_wire_seconds_total" in names
            assert "nns_element_counter_total" in names
            assert "nns_events_total" in names
            # tracer attached -> its report is flattened as nns_trace
            assert tracer is p.tracer
            assert "nns_trace" in names
            # per-element counters carry this pipeline's buffers
            got = sum(v for (n, lab), v in samples.items()
                      if n == "nns_element_counter_total"
                      and dict(lab).get("element") == "out"
                      and dict(lab).get("counter") == "buffers")
            assert got == 3
        finally:
            p.stop()

    def test_serve_scheduler_series_scraped_mid_run(self):
        obs_metrics.reset()
        port = _free_port()
        server = parse_launch(
            f'tensor_serve_src name=src port={port} id=91 buckets=1,2,4 '
            'max-wait-ms=2 '
            '! tensor_filter framework=jax model=zoo://mlp?dtype=float32 '
            '! tensor_serve_sink id=91')
        server.start()
        time.sleep(0.2)
        client = parse_launch(
            f'appsrc name=in caps="{CAPS64}" '
            f'! tensor_query_client name=qc port={port} timeout=15 '
            'max-request=8 ! appsink name=out')
        client.start()
        try:
            for i in range(8):
                client["in"].push_buffer(Buffer.from_arrays(
                    [np.full(64, float(i), np.float32)]))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    len(client["out"].buffers) \
                    + client["qc"].stats["shed"] < 8:
                time.sleep(0.05)
            # scrape while the scheduler is live: occupancy gauges and
            # queue-delay quantiles are present as series
            samples = obs_metrics.parse(obs_metrics.render())
            names = {n for (n, _l) in samples}
            assert "nns_serve_depth" in names
            assert "nns_serve_streams" in names
            assert "nns_serve_occupancy_avg" in names
            assert any(n == "nns_serve_queue_delay_us"
                       and dict(lab).get("quantile") == "p50"
                       for (n, lab) in samples)
        finally:
            client["in"].end_stream()
            client.stop()
            server.stop()


class TestMetricsServer:
    def test_routes(self):
        srv = MetricsServer(port=0).start()
        try:
            body = scrape("localhost", srv.bound_port)
            assert obs_metrics.parse(body) is not None
            assert scrape("localhost", srv.bound_port,
                          path="/healthz") == "ok\n"
            doc = json.loads(scrape("localhost", srv.bound_port,
                                    path="/flight"))
            assert "traceEvents" in doc
            with pytest.raises(ConnectionError):
                scrape("localhost", srv.bound_port, path="/nope")
            assert srv.scrapes == 4
        finally:
            srv.stop()

    def test_broker_registration_discovers_endpoint(self):
        from nnstreamer_tpu.edge.broker import DiscoveryBroker, \
            discover_meta
        broker = DiscoveryBroker(port=0)
        broker.start()
        srv = None
        try:
            from nnstreamer_tpu import obs
            srv = obs.serve_metrics(
                broker=("localhost", broker.bound_port),
                labels={"zone": "z1"})
            eps = discover_meta("localhost", broker.bound_port, "obs")
            assert [(h, p, m.get("role"), m.get("zone"))
                    for (h, p), m in eps] == \
                [("127.0.0.1", srv.bound_port, "obs", "z1")]
        finally:
            if srv is not None:
                srv.stop()
            broker.stop()

    def test_top_renders_one_row_per_endpoint(self, capsys):
        srv = MetricsServer(port=0).start()
        try:
            rc = obs_top.main(
                ["--targets", f"localhost:{srv.bound_port}", "--json"])
            assert rc == 0
            rows = json.loads(capsys.readouterr().out)
            assert len(rows) == 1
            assert rows[0]["endpoint"] == f"localhost:{srv.bound_port}"
            # unreachable targets degrade to a row, not a crash
            rc = obs_top.main(
                ["--targets", f"localhost:{_free_port()}", "--json"])
            assert rc == 0
            rows = json.loads(capsys.readouterr().out)
            assert "unreachable" in str(rows[0]["events"])
        finally:
            srv.stop()

    def test_top_table_formats(self):
        table = obs_top.render_table([
            {"endpoint": "a:1", "depth": 1.0, "fps": float("nan")}])
        lines = table.splitlines()
        assert lines[0].startswith("ENDPOINT")
        assert "a:1" in lines[1]


# ---------------------------------------------------------- wire trace

class TestWireTraceField:
    def _buf(self, v=1.0, ctx=None):
        buf = Buffer.from_arrays([np.full(4, v, np.float32)])
        if ctx is not None:
            obs_ctx.attach(buf, ctx)
        return buf

    def test_untraced_link_is_byte_identical(self):
        # a stamped buffer packed WITHOUT trace negotiation must produce
        # exactly the traffic an un-instrumented build produces
        ctx = obs_ctx.TraceContext(obs_ctx.next_id(), 5, time.time_ns())
        plain_cfg = wire.WireConfig()
        assert plain_cfg.trace is False
        meta, payloads = wire.pack_buffer(self._buf(ctx=ctx), plain_cfg)
        assert "trace" not in meta
        bmeta, bpayloads = wire.pack_batch(
            [self._buf(1.0, ctx), self._buf(2.0)], plain_cfg)
        assert "fhdr" not in bmeta and "ts" not in bmeta
        assert len(bytes(bpayloads[0])) == wire._FHDR.size * 2
        # and the meta block itself advertises nothing trace-shaped
        assert "trace" not in plain_cfg.to_meta()

    def test_negotiation_requires_both_peers(self):
        assert wire.advertise()["trace"] is True      # obs on: advertise
        old_peer = {"v": 2, "codec": "raw", "precision": "none",
                    "codecs": ["raw"], "precisions": ["none"]}
        assert wire.negotiate(old_peer).trace is False
        new_peer = dict(old_peer, trace=True)
        assert wire.negotiate(new_peer).trace is True
        assert wire.accept(old_peer).trace is False
        assert wire.accept(new_peer).trace is True

    def test_data_meta_field_re_links_and_attributes_wire_time(self):
        ctx = obs_ctx.TraceContext(obs_ctx.next_id(), 0, time.time_ns())
        obs_spans.record_root("sender", ctx)
        sent_span = ctx.span_id
        cfg = wire.WireConfig(trace=True)
        meta, payloads = wire.pack_buffer(self._buf(ctx=ctx), cfg)
        assert meta["trace"][0] == ctx.trace_id
        back = wire.unpack_buffer(meta, payloads)
        got = obs_ctx.ctx_of(back)
        assert got is not None and got is not ctx
        assert got.trace_id == ctx.trace_id
        assert got.w_ns >= 0
        # the receiver recorded a wire span parented on the sender's
        # last span — the cross-process link in the tree
        spans = _spans_by_trace([ctx.trace_id])[ctx.trace_id]
        wire_spans = [s for s in spans if s[1] == "wire"]
        assert len(wire_spans) == 1
        assert wire_spans[0][6] == sent_span
        _assert_tree(spans)

    def test_batch_fhdr2_round_trips_contexts_per_frame(self):
        ctxs = [obs_ctx.TraceContext(obs_ctx.next_id(), i + 1,
                                     time.time_ns(), q_ns=i)
                for i in range(3)]
        bufs = [self._buf(float(i), c) for i, c in enumerate(ctxs)]
        bufs.append(self._buf(9.0))                  # one untraced frame
        cfg = wire.WireConfig(trace=True)
        meta, payloads = wire.pack_batch(bufs, cfg)
        assert meta["fhdr"] == 2
        out = wire.unpack_batch(meta, payloads)
        assert len(out) == 4
        for i, (src, got) in enumerate(zip(ctxs, out)):
            ctx = obs_ctx.ctx_of(got)
            assert ctx.trace_id == src.trace_id
            assert ctx.q_ns == i                     # attribution rode
            assert ctx.w_ns > 0                      # transit attributed
        assert obs_ctx.ctx_of(out[3]) is None        # untraced stays so

    def test_edge_pipeline_carries_trace_end_to_end(self):
        obs_metrics.reset()
        port = _free_port()
        pub = parse_launch(
            f'appsrc name=in caps="{CAPS4}" '
            f'! edgesink name=p port={port} topic=t')
        pub.start()
        time.sleep(0.2)
        sub = parse_launch(
            f'edgesrc name=s dest-port={port} topic=t timeout=15 '
            '! appsink name=out')
        sub.start()
        time.sleep(0.3)
        for i in range(4):
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        deadline = time.monotonic() + 15
        while len(sub["out"].buffers) < 4 and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        pub["in"].end_stream()
        sub.wait_eos(timeout=15)
        sub.stop()
        pub.stop()
        bufs = sub["out"].buffers
        assert len(bufs) == 4
        traces = [obs_ctx.ctx_of(b).trace_id for b in bufs]
        assert len(set(traces)) == 4
        grouped = _spans_by_trace(traces)
        for b in bufs:
            ctx = obs_ctx.ctx_of(b)
            spans = grouped[ctx.trace_id]
            _assert_tree(spans)
            assert any(s[1] == "wire" for s in spans)
        # the subscriber's sink attributed wire time in its histogram
        samples = obs_metrics.parse(obs_metrics.render())
        wsum = sum(v for (n, lab), v in samples.items()
                   if n == "nns_e2e_wire_seconds_total"
                   and dict(lab).get("sink") == "out")
        assert wsum > 0.0


# ---------------------------- report-shape regression (satellite: the
# transfer/fusion blocks must agree on what "devices" means and always
# carry it, so dashboards can rely on the key)

class TestReportDevicesShape:
    def test_transfer_block_always_carries_devices(self):
        p = parse_launch(
            f'tensortestsrc caps="{CAPS4}" num-buffers=6 pattern=counter '
            '! queue ! tensor_filter name=f framework=simlink '
            'custom=rtt:5,svc:1 in-flight=4 ! appsink name=out')
        p.fuse = False
        tracer = p.enable_tracing()
        p.run(timeout=60)
        block = tracer.report(p)["transfer"]
        # per-chip overlap: devices present and == 1 (the regression:
        # it used to be absent unless a window reported a mesh span)
        assert block["devices"] == 1
        assert isinstance(block["devices"], int)
        assert set(block["windows"]) == {"f"}
        assert block["windows"]["f"]["completed"] == 6
        # the dispatcher/completer split recorded spans on both sides
        # of the thread boundary, still one connected tree per frame
        traces = [obs_ctx.ctx_of(b).trace_id for b in p["out"].buffers]
        grouped = _spans_by_trace(traces)
        for tid in traces:
            _assert_tree(grouped[tid])
            assert {"dispatch", "complete"} <= \
                {s[1] for s in grouped[tid]}

    def test_fusion_block_devices_is_max_over_segments(self):
        p = parse_launch(
            f'tensortestsrc caps="{CAPS4}" num-buffers=4 '
            '! tensor_transform name=a mode=arithmetic option=mul:2 '
            '! tensor_transform name=b mode=arithmetic option=add:1 '
            '! appsink name=out')
        tracer = p.enable_tracing()
        p.run(timeout=60)
        block = tracer.report(p)["fusion"]
        per_seg = list(block["per_segment"].values())
        assert per_seg, "expected at least one fused segment"
        for seg in per_seg:
            assert seg["devices"] >= 1
        assert block["devices"] == max(s["devices"] for s in per_seg)


# -------------------------------------- cross-process span tree merge

_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.obs.recorder import RECORDER

desc, dump_path = sys.argv[1], sys.argv[2]
p = parse_launch(desc)
p.start()
port = 0
for name in ("src", "rt"):
    el = p.elements.get(name)
    if el is not None and getattr(el, "bound_port", 0):
        port = el.bound_port
print(json.dumps({"ready": True, "port": port, "pid": os.getpid()}),
      flush=True)
sys.stdin.readline()                      # parent: dump and exit
p.stop()
RECORDER.dump(dump_path, window_s=600)
print("dumped", flush=True)
"""


def _spawn_child(desc, dump_path):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               NNS_TPU_FLIGHT_DIR="")
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD, desc, str(dump_path)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=env)
    line = proc.stdout.readline()
    try:
        info = json.loads(line)
    except json.JSONDecodeError:
        proc.kill()
        raise AssertionError(
            f"child failed to start: {line!r}\n{proc.stderr.read()}")
    return proc, info


def _dump_child(proc):
    proc.stdin.write("dump\n")
    proc.stdin.flush()
    assert proc.stdout.readline().strip() == "dumped", proc.stderr.read()
    proc.wait(timeout=30)


def _merge_events(docs):
    evs = []
    for doc in docs:
        assert "traceEvents" in doc            # valid Chrome trace
        evs.extend(doc["traceEvents"])
    return evs


def _assert_cross_process_tree(events, trace_hex, min_pids):
    mine = [e for e in events if e["ph"] == "X"
            and e.get("args", {}).get("trace") == trace_hex]
    assert mine, f"no spans for trace {trace_hex}"
    pids = {e["pid"] for e in mine}
    assert len(pids) >= min_pids, \
        f"trace {trace_hex} spans only pids {pids}"
    ids = {e["args"]["span"] for e in mine}
    roots = [e for e in mine if e["args"]["parent"] == "0"]
    assert len(roots) == 1, f"want one root, got {len(roots)}"
    for e in mine:
        assert e["args"]["parent"] == "0" or e["args"]["parent"] in ids, \
            f"orphan span {e}"
    return mine


class TestCrossProcessSpanTree:
    def test_client_to_replica_two_process_tree(self, tmp_path):
        """The light arm (tier-1): a client frame served by a child
        replica process comes back with a context whose merged span
        tree (parent dump + child dump) is one connected tree across
        two pids."""
        RECORDER.clear()
        dump = tmp_path / "replica.json"
        proc, info = _spawn_child(
            "tensor_serve_src name=src port=0 id=93 buckets=1,2,4 "
            "max-wait-ms=2 "
            "! tensor_filter framework=jax model=zoo://mlp?dtype=float32 "
            "! tensor_serve_sink id=93", dump)
        client = None
        try:
            client = parse_launch(
                f'appsrc name=in caps="{CAPS64}" '
                f'! tensor_query_client name=qc port={info["port"]} '
                'timeout=15 max-request=8 ! appsink name=out')
            client.start()
            for i in range(6):
                client["in"].push_buffer(Buffer.from_arrays(
                    [np.full(64, float(i), np.float32)]))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    len(client["out"].buffers) < 6:
                time.sleep(0.05)
            bufs = client["out"].buffers
            assert len(bufs) == 6
            ctxs = [obs_ctx.ctx_of(b) for b in bufs]
            assert all(c is not None for c in ctxs)
            _dump_child(proc)
            client["in"].end_stream()
            client.stop()
            client = None
            with open(dump) as f:
                child_doc = json.load(f)
            events = _merge_events(
                [RECORDER.dump(window_s=600), child_doc])
            for ctx in ctxs:
                mine = _assert_cross_process_tree(
                    events, f"{ctx.trace_id:x}", min_pids=2)
                # the serve scheduler's spans are in the child's half
                cats = {e["cat"] for e in mine
                        if e["pid"] == info["pid"]}
                assert "wire" in {e["cat"] for e in mine}
                assert cats, "no spans recorded in the replica process"
        finally:
            if client is not None:
                client.stop()
            if proc.poll() is None:
                proc.kill()

    @pytest.mark.slow
    def test_router_replica_mesh_three_process_tree(self, tmp_path):
        """The acceptance arm: client -> router (child) -> replica
        (child) with a mesh-sharded fused segment -> response. The
        merged per-process flight dumps are valid Chrome trace JSON
        forming ONE connected span tree across >=3 pids."""
        RECORDER.clear()
        rep_dump = tmp_path / "replica.json"
        rt_dump = tmp_path / "router.json"
        rep_proc, rep_info = _spawn_child(
            "tensor_serve_src name=src port=0 id=94 buckets=1,2,4,8 "
            "mesh=8x1x1 max-wait-ms=2 max-queue=8 retry-after-ms=10 "
            "! tensor_filter framework=jax model=zoo://mlp?dtype=float32 "
            "custom=mesh:8x1x1 ! tensor_serve_sink id=94", rep_dump)
        rt_proc = client = None
        try:
            rt_proc, rt_info = _spawn_child(
                f"tensor_serve_router name=rt port=0 "
                f"replicas=localhost:{rep_info['port']}", rt_dump)
            client = parse_launch(
                f'appsrc name=in caps="{CAPS64}" '
                f'! tensor_query_client name=qc port={rt_info["port"]} '
                'timeout=20 max-request=8 ! appsink name=out')
            client.start()
            for i in range(8):
                client["in"].push_buffer(Buffer.from_arrays(
                    [np.full(64, float(i), np.float32)]))
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and \
                    len(client["out"].buffers) \
                    + client["qc"].stats["shed"] < 8:
                time.sleep(0.05)
            bufs = client["out"].buffers
            assert bufs, "mesh-served fleet returned nothing"
            ctxs = [obs_ctx.ctx_of(b) for b in bufs]
            assert all(c is not None for c in ctxs)
            _dump_child(rt_proc)
            _dump_child(rep_proc)
            client["in"].end_stream()
            client.stop()
            client = None
            with open(rt_dump) as f:
                rt_doc = json.load(f)
            with open(rep_dump) as f:
                rep_doc = json.load(f)
            events = _merge_events(
                [RECORDER.dump(window_s=600), rt_doc, rep_doc])
            linked = 0
            for ctx in ctxs:
                mine = _assert_cross_process_tree(
                    events, f"{ctx.trace_id:x}", min_pids=3)
                pids = {e["pid"] for e in mine}
                assert {rt_info["pid"], rep_info["pid"],
                        os.getpid()} <= pids
                linked += 1
            assert linked == len(bufs)
        finally:
            if client is not None:
                client.stop()
            for proc in (rt_proc, rep_proc):
                if proc is not None and proc.poll() is None:
                    proc.kill()
