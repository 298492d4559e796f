"""Tracing tests (≙ GstShark proctime/interlatency/framerate tracers,
reference tools/tracing/README.md)."""
import time

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters import register_custom_easy
from nnstreamer_tpu.obs import context as obs_ctx
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import spans as obs_spans
from nnstreamer_tpu.tensors import TensorsInfo

CAPS = ("other/tensors,format=static,num_tensors=1,types=float32,"
        "dimensions=8,framerate=0/1")


def test_tracer_reports_all_elements():
    register_custom_easy(
        "slow10ms", lambda x: (time.sleep(0.01), x)[1],
        TensorsInfo.make("float32", "8"), TensorsInfo.make("float32", "8"))
    p = nt.parse_launch(
        f"tensortestsrc caps={CAPS} num-buffers=5 ! "
        "queue name=q max-size-buffers=4 ! "
        "tensor_filter name=f framework=custom-easy model=slow10ms ! "
        "appsink name=out")
    tracer = p.enable_tracing()
    p.run(20)
    rep = tracer.report(p)
    assert {"q", "f", "out"} <= set(rep)
    # interlatency grows downstream: the sink sees the buffer later
    # than the filter, which sees it later than the queue
    assert rep["out"]["interlatency_us_avg"] >= \
        rep["f"]["interlatency_us_avg"] >= rep["q"]["interlatency_us_avg"]
    # the slow filter dominates: its downstream interlatency >= ~10ms
    assert rep["out"]["interlatency_us_avg"] >= 9000
    assert rep["f"]["proctime_us_avg"] >= 9000
    assert rep["out"]["buffers"] == 5
    assert rep["out"]["framerate_fps"] > 0


def test_tracing_off_by_default_no_overhead_keys():
    p = nt.parse_launch(
        f"tensortestsrc caps={CAPS} num-buffers=2 ! appsink name=out")
    p.run(10)
    assert p.tracer is None
    assert not any(k.startswith("_trace") for k in
                   p["out"].buffers[0].extras)


def test_interlatency_survives_fresh_buffers():
    """Elements that build brand-new Buffers (tensor_converter here)
    must not reset the birth stamp — the sink's interlatency includes
    everything upstream of them."""
    register_custom_easy(
        "slow5ms", lambda x: (time.sleep(0.005), x)[1],
        TensorsInfo.make("float32", "3:4:2"),
        TensorsInfo.make("float32", "3:4:2"))
    p = nt.parse_launch(
        'videotestsrc num-buffers=4 pattern=smpte '
        'caps="video/x-raw,format=RGB,width=4,height=2,framerate=30/1" ! '
        "tensor_converter ! tensor_transform mode=typecast "
        "option=float32 ! "
        "tensor_filter framework=custom-easy model=slow5ms ! "
        "appsink name=out")
    tracer = p.enable_tracing()
    p.run(20)
    rep = tracer.report(p)
    # converter rebuilds the buffer; without birth inheritance the sink
    # would report near-zero instead of >= the filter's 5 ms sleep
    assert rep["out"]["interlatency_us_avg"] >= 4500, rep["out"]


def _slow(name, ms, dims="8"):
    register_custom_easy(
        name, lambda x: (time.sleep(ms / 1e3), x)[1],
        TensorsInfo.make("float32", dims), TensorsInfo.make("float32", dims))
    return name


def _e2e_of(sink):
    """(count, mean seconds) of the e2e histogram a scrape shows for
    ``sink``."""
    samples = obs_metrics.parse(obs_metrics.render())
    pick = {n: v for (n, lab), v in samples.items()
            if dict(lab).get("sink") == sink}
    n = pick.get("nns_e2e_latency_seconds_count", 0)
    return n, (pick["nns_e2e_latency_seconds_sum"] / n if n else 0.0)


def test_one_birth_stamp_report_agrees_with_e2e_histogram():
    """Tracing keeps no stamp of its own: a traced buffer carries the
    one observability key, and the sink's interlatency is the e2e
    histogram's latency (same birth, same clock; the sink's own chain
    time, microseconds here, is all that parts them)."""
    obs_metrics.reset()
    p = nt.parse_launch(
        f"tensortestsrc caps={CAPS} num-buffers=6 ! "
        f"tensor_filter framework=custom-easy model={_slow('slow8ms', 8)} ! "
        "queue ! appsink name=e2eout")
    tracer = p.enable_tracing()
    p.run(20)
    for buf in p["e2eout"].buffers:
        assert [k for k in buf.extras if k.startswith(("_obs", "_trace"))] \
            == [obs_ctx.CTX_KEY]
    rep = tracer.report(p)["e2eout"]
    n, mean_s = _e2e_of("e2eout")
    assert n == rep["buffers"] == 6
    assert rep["interlatency_us_avg"] >= 7000
    assert abs(rep["interlatency_us_avg"] - mean_s * 1e6) < 1000


def test_tracing_reports_with_obs_off():
    """NNS_TPU_OBS=0 and enable_tracing(): the stamp and the aggregate
    run for that pipeline, the rings and the e2e histograms stay off."""
    obs_spans.set_enabled(False)
    try:
        obs_spans.clear()
        obs_metrics.reset()
        p = nt.parse_launch(
            f"tensortestsrc name=src caps={CAPS} num-buffers=5 ! "
            "tensor_filter name=f framework=custom-easy "
            f"model={_slow('slow10ms_off', 10)} ! queue name=q ! "
            "identity name=id ! appsink name=offout")
        tracer = p.enable_tracing()
        p.run(20)
        rep = tracer.report(p)
        for name in ("f", "q", "id", "offout"):
            assert rep[name]["buffers"] == 5, name
            assert rep[name]["interlatency_us_avg"] > 0, name
        for name in ("q", "id", "offout"):     # behind the 10 ms filter
            assert rep[name]["interlatency_us_avg"] >= 9000, name
        assert rep["offout"]["framerate_fps"] > 0
        # fed by the one stamp: the context, and nothing beside it
        assert set(p["offout"].buffers[0].extras) == {obs_ctx.CTX_KEY}
        assert obs_spans.snapshot() == []
        assert _e2e_of("offout") == (0, 0.0)
        # an untraced pipeline beside it still does no observability work
        p2 = nt.parse_launch(
            f"tensortestsrc caps={CAPS} num-buffers=2 ! appsink name=out")
        p2.run(10)
        assert not p2["out"].buffers[0].extras
    finally:
        obs_spans.set_enabled(True)


def test_fresh_buffer_behind_a_queue_inherits_its_own_frame():
    """A queue worker runs frame after frame on one thread: an element
    that mints a fresh buffer there must hand on THIS frame's context
    (made current as its chain begins), not the one the last frame left
    behind, or every interlatency behind it is off by a frame."""
    from nnstreamer_tpu.pipeline.element import TransformElement
    from nnstreamer_tpu.pipeline.registry import register_element
    from nnstreamer_tpu.tensors.buffer import Buffer

    @register_element("test_trace_freshen")
    class Freshen(TransformElement):
        def transform(self, buf):
            return Buffer(list(buf.chunks), pts=buf.pts)

    p = nt.parse_launch(
        f"tensortestsrc caps={CAPS} num-buffers=5 ! identity name=tap ! "
        "queue ! test_trace_freshen ! appsink name=out")
    born, inner = [], p["tap"].transform

    def tap(buf):
        born.append(obs_ctx.ctx_of(buf).trace_id)
        return inner(buf)

    p["tap"].transform = tap
    p.run(20)
    seen = [obs_ctx.ctx_of(b) for b in p["out"].buffers]
    assert all(c is not None for c in seen)
    assert [c.trace_id for c in seen] == born and len(set(born)) == 5
