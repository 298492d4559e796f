"""Fusion compiler: planner boundaries, byte parity, jit cache, faults.

The contract under test (fusion/): maximal runs of device-capable
elements collapse into one FusedSegment whose jitted program is
byte-identical to the per-element chain path on the CPU backend. The
per-element path stays available as ``fuse=false`` — every parity test
here runs the SAME description both ways and compares raw bytes.
"""
import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.analysis import Severity, analyze
from nnstreamer_tpu.fusion import FusedSegment, fuse_pipeline, plan_fusion
from nnstreamer_tpu.pipeline.element import TransformElement
from nnstreamer_tpu.pipeline.pipeline import Pipeline
from nnstreamer_tpu.pipeline.registry import make_element
from nnstreamer_tpu.tensors.caps import Caps

CAPS_F32 = ("other/tensors,format=static,num_tensors=1,"
            "types=(string)float32,dimensions=(string)3:4:4,"
            "framerate=(fraction)0/1")
CAPS_U8 = ("other/tensors,format=static,num_tensors=1,"
           "types=(string)uint8,dimensions=(string)3:4:4,"
           "framerate=(fraction)0/1")
CAPS_SEG = ("other/tensors,format=static,num_tensors=1,"
            "types=(string)float32,dimensions=(string)8:8,"
            "framerate=(fraction)0/1")
CAPS_F64 = ("other/tensors,format=static,num_tensors=1,"
            "types=(string)float64,dimensions=(string)3:4:4,"
            "framerate=(fraction)0/1")

# a fusible two-transform run used by several planner tests
RUN2 = ("tensor_transform name=a mode=arithmetic option=mul:2 ! "
        "tensor_transform name=b mode=transpose option=1:0:2")


def _segments_of(p):
    return [e for e in p.elements.values()
            if getattr(e, "IS_FUSED_SEGMENT", False)]


def _run(desc, fuse=True, timeout=60):
    p = parse_launch(desc)
    p.fuse = fuse
    p.run(timeout=timeout)
    return p


def _frames(p, sink="out"):
    """appsink contents as comparable (dtype, shape, bytes) tuples."""
    out = []
    for buf in p[sink].pop_all():
        out.append(tuple(
            (str(np.asarray(c.host()).dtype), np.asarray(c.host()).shape,
             np.ascontiguousarray(c.host()).tobytes())
            for c in buf.chunks))
    return out


def assert_parity(desc, sink="out", min_frames=1):
    fused = _run(desc, fuse=True)
    plain = _run(desc, fuse=False)
    assert not _segments_of(plain)
    a, b = _frames(fused, sink), _frames(plain, sink)
    assert len(a) == len(b) >= min_frames
    assert a == b, "fused output is not byte-identical to the chain path"
    return fused


class TestPlannerBoundaries:
    def test_transform_run_fuses_sources_and_sinks_break(self):
        p = parse_launch(f"tensortestsrc name=src caps={CAPS_F32} ! "
                         f"{RUN2} ! appsink name=out")
        plan = plan_fusion(p)
        assert [s.names for s in plan.segments] == [["a", "b"]]
        assert "source" in plan.vetoes["src"]
        assert "sink" in plan.vetoes["out"]

    def test_queue_is_a_thread_boundary(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! "
                         "tensor_transform name=a mode=arithmetic "
                         "option=mul:2 ! queue name=q ! "
                         "tensor_transform name=b mode=arithmetic "
                         "option=add:1 ! appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "thread boundary" in plan.vetoes["q"]
        assert "run of 1" in plan.vetoes["a"]

    def test_run_of_one_is_left_on_the_chain_path(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! "
                         "tensor_transform name=a mode=arithmetic "
                         "option=mul:2 ! appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "run of 1" in plan.vetoes["a"]

    def test_elements_without_device_fn_break_runs(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! "
                         "tensor_transform name=a mode=arithmetic "
                         "option=mul:2 ! identity name=i ! "
                         "tensor_transform name=b mode=arithmetic "
                         "option=add:1 ! appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "no device function" in plan.vetoes["i"]

    def test_multi_pad_elements_are_structural_boundaries(self):
        p = parse_launch(
            "tensor_mux name=m ! appsink name=out "
            f"tensortestsrc caps={CAPS_F32} ! m.sink_0 "
            f"tensortestsrc caps={CAPS_F32} ! m.sink_1")
        plan = plan_fusion(p)
        assert "1-in/1-out" in plan.vetoes["m"]

    def test_64bit_dtype_is_a_caps_boundary(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F64} ! {RUN2} ! "
                         "appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "x64" in plan.vetoes["a"]

    def test_dynamic_caps_break_downstream_of_crop(self):
        # crop emits FLEXIBLE caps: transforms after it cannot join a
        # static jit program
        p = parse_launch(
            f"tensortestsrc caps={CAPS_F32} ! tensor_crop name=c "
            "c.src ! tensor_transform name=a mode=arithmetic option=mul:2 "
            "! tensor_transform name=b mode=arithmetic option=add:1 ! "
            "appsink name=out "
            "tensortestsrc caps=other/tensors,format=static,num_tensors=1,"
            "types=(string)uint32,dimensions=(string)4,"
            "framerate=(fraction)0/1 ! c.info")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "1-in/1-out" in plan.vetoes["c"]  # structural veto first
        assert "a" in plan.vetoes

    def test_on_error_policy_change_splits_the_run(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! "
                         "tensor_transform name=a mode=arithmetic "
                         "option=mul:2 on_error=skip ! "
                         "tensor_transform name=b mode=arithmetic "
                         "option=add:1 ! appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "policy" in plan.vetoes["b"]

    def test_uniform_policy_run_fuses_whole(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! "
                         "tensor_transform name=a mode=arithmetic "
                         "option=mul:2 on_error=skip ! "
                         "tensor_transform name=b mode=arithmetic "
                         "option=add:1 on_error=skip ! "
                         "tensor_transform name=c mode=transpose "
                         "option=1:0:2 on_error=skip ! appsink name=out")
        plan = plan_fusion(p)
        assert [s.names for s in plan.segments] == [["a", "b", "c"]]

    def test_invoke_dynamic_filter_declines(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_SEG} ! "
                         "tensor_filter name=f framework=jax "
                         "model=zoo://toyseg invoke-dynamic=true ! "
                         "tensor_decoder name=d mode=image_segment ! "
                         "appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "invoke-dynamic" in plan.vetoes["f"]

    def test_host_only_decoder_mode_declines(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! "
                         "tensor_transform name=a mode=arithmetic "
                         "option=mul:2 ! tensor_decoder name=d "
                         "mode=direct_video ! appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "host-only" in plan.vetoes.get("d", "host-only")

    def test_stand_mode_is_vetoed_for_parity(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! "
                         "tensor_transform name=a mode=arithmetic "
                         "option=mul:2 ! tensor_transform name=s "
                         "mode=stand option=default ! appsink name=out")
        plan = plan_fusion(p)
        assert plan.segments == []
        assert "byte-stable" in plan.vetoes["s"]


class TestOptOut:
    def test_fuse_false_launch_prop(self):
        p = parse_launch(f"fuse=false tensortestsrc caps={CAPS_F32} "
                         f"num-buffers=2 ! {RUN2} ! appsink name=out")
        assert p.fuse is False
        p.run(timeout=60)
        assert not _segments_of(p)
        assert p._fusion_plan is None

    def test_fuse_attr_opt_out(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} num-buffers=2 ! "
                         f"{RUN2} ! appsink name=out")
        p.fuse = False
        p.run(timeout=60)
        assert not _segments_of(p)

    def test_fused_members_stay_addressable(self):
        p = _run(f"tensortestsrc caps={CAPS_F32} num-buffers=3 ! {RUN2} ! "
                 "appsink name=out")
        assert len(_segments_of(p)) == 1
        # members keep their names, stats, and pipeline membership
        assert p["a"].stats["buffers"] == 0  # data bypassed the chain path
        assert p._fusion_plan.summary()["segments"] == [["a", "b"]]


class TestParity:
    def test_filter_decoder_chain(self):
        # the acceptance chain: model invoke + argmax decode in ONE
        # device program, byte-identical to two host round trips
        p = assert_parity(
            f"tensortestsrc caps={CAPS_SEG} num-buffers=4 ! "
            "tensor_filter framework=jax model=zoo://toyseg ! "
            "tensor_decoder mode=image_segment ! appsink name=out",
            min_frames=4)
        segs = _segments_of(p)
        assert len(segs) == 1
        assert segs[0].stats["fused_elements"] == 2

    def test_transform_chain(self):
        assert_parity(
            f"tensortestsrc caps={CAPS_U8} num-buffers=4 ! "
            "tensor_transform mode=typecast option=float32 ! "
            "tensor_transform mode=arithmetic option=mul:2,add:1 ! "
            "tensor_transform mode=transpose option=1:0:2 ! "
            "appsink name=out", min_frames=4)

    def test_mux_and_transform_chain(self):
        # mux itself stays on the host; the transform run after it fuses
        p = assert_parity(
            "tensor_mux name=m ! "
            "tensor_transform name=a mode=typecast option=float32 ! "
            "tensor_transform name=b mode=arithmetic option=div:2 ! "
            "appsink name=out "
            f"tensortestsrc caps={CAPS_U8} num-buffers=3 ! m.sink_0 "
            f"tensortestsrc caps={CAPS_U8} num-buffers=3 ! m.sink_1",
            min_frames=3)
        assert p._fusion_plan.summary()["segments"] == [["a", "b"]]

    def test_crop_fed_by_fused_transforms(self):
        # transforms upstream of the (host-side) crop fuse; the cropped
        # bytes must be identical either way
        desc = (
            "tensor_crop name=c ! appsink name=out "
            f"tensortestsrc caps={CAPS_U8} num-buffers=5 ! "
            "tensor_transform name=a mode=typecast option=float32 ! "
            "tensor_transform name=b mode=arithmetic option=mul:2 ! "
            "c.raw "
            "tensortestsrc caps=other/tensors,format=static,num_tensors=1,"
            "types=(string)uint32,dimensions=(string)4,"
            "framerate=(fraction)0/1 num-buffers=5 ! c.info")
        p = assert_parity(desc)
        assert len(_segments_of(p)) == 1

    def test_typecast_to_uint8_parity(self):
        # float -> int casts are where numpy and XLA most easily
        # diverge; the dtype-stability gate must keep the fused program
        # byte-exact or keep the element on the host
        assert_parity(
            f"tensortestsrc caps={CAPS_U8} num-buffers=4 ! "
            "tensor_transform mode=typecast option=float32 ! "
            "tensor_transform mode=arithmetic option=add:3 ! "
            "appsink name=out", min_frames=4)


class TestJitCache:
    def test_one_compile_then_hits(self):
        p = _run(f"tensortestsrc caps={CAPS_F32} num-buffers=6 ! {RUN2} ! "
                 "appsink name=out")
        seg = _segments_of(p)[0]
        assert seg.stats["jit_misses"] == 1
        assert seg.stats["jit_hits"] == 5

    def test_report_carries_fusion_block(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} num-buffers=4 ! "
                         f"{RUN2} ! appsink name=out")
        tracer = p.enable_tracing()
        p.run(timeout=60)
        rep = tracer.report(p)
        fb = rep["fusion"]
        assert fb["segments"] == 1
        assert fb["fused_elements"] == 2
        assert fb["jit_misses"] == 1
        assert fb["jit_hits"] == 3
        (seg_entry,) = fb["per_segment"].values()
        assert seg_entry["members"] == ["a", "b"]
        assert "dispatch_us_p50" in seg_entry

    def test_unfused_report_has_no_fusion_block(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} num-buffers=2 ! "
                         f"{RUN2} ! appsink name=out")
        p.fuse = False
        tracer = p.enable_tracing()
        p.run(timeout=60)
        assert "fusion" not in tracer.report(p)


class BoomDevice(TransformElement):
    """Test element: fuses eagerly, then its device program raises on
    every dispatch — the segment-level fault-path probe."""

    PROPS = {"breaker-threshold": 0, "breaker-reset-ms": 1000.0,
             "breaker-retry-after-ms": 100.0}

    def transform(self, buf):
        return buf

    def device_fn(self, ctx=None):
        def fn(arrays):
            raise RuntimeError("injected device fault")
        return fn


class PassDevice(TransformElement):
    def transform(self, buf):
        return buf

    def device_fn(self, ctx=None):
        return lambda arrays: arrays


def _boom_pipeline(n=4, **boom_props):
    p = Pipeline()
    src = make_element("tensortestsrc", name="src")
    src.set_property("caps", CAPS_F32)
    src.set_property("num-buffers", n)
    sink = make_element("appsink", name="out")
    boom = BoomDevice(name="boom", **boom_props)
    ok = PassDevice(name="ok", on_error=str(boom_props.get("on_error",
                                                           "fail")))
    p.add(src, boom, ok, sink)
    p.link(src, boom, ok, sink)
    return p


class TestSegmentFaults:
    def test_device_fault_escalates_under_default_policy(self):
        p = _boom_pipeline()
        p.start()
        assert len(_segments_of(p)) == 1
        with pytest.raises(RuntimeError, match="injected device fault"):
            p.wait_eos(timeout=30)
        p.stop()

    def test_skip_policy_drops_faulted_frames(self):
        p = _boom_pipeline(on_error="skip")
        p.start()
        p.wait_eos(timeout=30)
        p.stop()
        seg = _segments_of(p)[0]
        assert seg.stats["dropped"] == 4
        assert p["out"].buffers == []

    def test_breaker_opens_and_sheds(self):
        p = _boom_pipeline(
            n=8, on_error="skip", **{"breaker-threshold": 2})
        p.start()
        p.wait_eos(timeout=30)
        p.stop()
        seg = _segments_of(p)[0]
        assert seg.stats["breaker_opened"] >= 1
        # after 2 failures the breaker opens: later frames shed without
        # paying a doomed dispatch
        assert seg.stats["shed"] >= 1
        assert seg.stats["dropped"] == 8


class LyingTransform(TransformElement):
    """Declares a device_fn but its static transfer contradicts the
    chain path's transform_caps — the fusion-transfer lint rule's
    target."""

    def transform(self, buf):
        return buf

    def transform_caps(self, incaps):
        return incaps

    def static_transfer(self, in_caps):
        return {"src": Caps(CAPS_U8).fixate()}

    def device_fn(self, ctx=None):
        return lambda arrays: arrays


class TestLintRules:
    def test_fusion_break_warns_on_single_blocker(self):
        p = parse_launch(  # pipelint: skip — deliberate fusion break
            f"tensortestsrc caps={CAPS_F32} ! "
            "tensor_transform name=a mode=arithmetic option=mul:2 ! "
            "identity name=i ! "
            "tensor_transform name=b mode=arithmetic option=add:1 ! "
            "appsink name=out")
        got = [f for f in analyze(p).findings if f.rule == "fusion-break"]
        assert len(got) == 1
        assert got[0].element == "i"
        assert got[0].severity is Severity.WARNING
        assert "'a'" in got[0].message and "'b'" in got[0].message

    def test_fusible_chain_is_clean(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} ! {RUN2} ! "
                         "appsink name=out")
        assert [f for f in analyze(p).findings
                if f.rule in ("fusion-break", "fusion-transfer")] == []

    def test_fusion_transfer_mismatch_is_an_error(self):
        p = Pipeline()
        src = make_element("tensortestsrc", name="src")
        src.set_property("caps", CAPS_F32)
        liar = LyingTransform(name="liar")
        sink = make_element("appsink", name="out")
        p.add(src, liar, sink)
        p.link(src, liar, sink)
        got = [f for f in analyze(p).findings if f.rule == "fusion-transfer"]
        assert len(got) == 1
        assert got[0].element == "liar"
        assert got[0].severity is Severity.ERROR


class TestLifecycle:
    def test_restart_does_not_refuse_or_double_fuse(self):
        p = parse_launch(f"tensortestsrc caps={CAPS_F32} num-buffers=2 ! "
                         f"{RUN2} ! appsink name=out")
        p.start()
        assert len(_segments_of(p)) == 1
        p.stop()
        p.start()  # plan is sticky: no second rewiring
        assert len(_segments_of(p)) == 1
        p.stop()

    def test_fusion_failure_fails_the_launch(self, monkeypatch):
        """A planner failure is loud: running unfused instead would
        deliver from a slower path and say nothing. fuse=false is the
        explicit way onto the chain path."""
        import nnstreamer_tpu.fusion as fusion
        monkeypatch.setattr(
            fusion, "fuse_pipeline",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
        desc = (f"tensortestsrc caps={CAPS_F32} num-buffers=2 ! "
                f"{RUN2} ! appsink name=out")
        with pytest.raises(RuntimeError, match="boom"):
            parse_launch(desc).start()
        p = parse_launch(desc)
        p.fuse = False
        p.run(timeout=60)
        assert not _segments_of(p)
        assert len(p["out"].buffers) == 2
