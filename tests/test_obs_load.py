"""The load path measures itself (ISSUE 36): ``nns.load.*`` spans from
``start()`` to the first buffer, every ``jax.monitoring`` compile event
charged to the open program of its thread (``obs/load.py``), one record
a program in ``transfer_report()["load"]``, ``nns_load_seconds`` on
``/metrics``. Durations are asserted as orderings and sums, never as
sizes.
"""
import glob
import os
import threading
import time

import numpy as np
import pytest

import jax

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.obs import RECORDER
from nnstreamer_tpu.obs import load as obs_load
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import spans as obs_spans

CAPS16 = ('"other/tensors,format=static,num_tensors=1,'
          'types=(string)float32,dimensions=(string)16,'
          'framerate=(fraction)0/1"')
MLP = '"zoo://mlp?in_dim=16&hidden=32&out_dim=4&dtype=float32"'
# float32 leaves used in bfloat16: the load has a prepare program
VIT = "zoo://vit?size=32&patch=8&d_model=64&layers=2&heads=4&classes=10"
VIT_CAPS = ('"other/tensors,format=static,num_tensors=1,'
            'types=(string)uint8,dimensions=(string)3:32:32:4,'
            'framerate=(fraction)0/1"')
SPANS = ("nns.load.start", "nns.load.model", "nns.load.place",
         "nns.load.program", "nns.load.trace", "nns.filter.prepare",
         "nns.load.first_buffer")
RECORD_KEYS = {"program", "signature", "donate", "at", "wall_s", "trace_s",
               "lower_s", "compile_s", "cache", "retrieval_s", "prepare_s"}
SLACK = 1e-3      # an event's end is stamped in the listener, just after


def _push(pipe, array, want):
    pipe["in"].push_buffer(Buffer.from_arrays([array]))
    deadline = time.monotonic() + 120
    while len(pipe["out"].buffers) < want and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(pipe["out"].buffers) == want


def _mlp(extra=""):
    pipe = parse_launch(
        f"appsrc name=in caps={CAPS16} ! tensor_filter name=f "
        f"framework=jax model={MLP} {extra} ! appsink name=out")
    pipe.start()
    return pipe


def _stop(pipe):
    pipe["in"].end_stream()
    pipe.stop()


@pytest.fixture
def vit_load():
    """A windowed ViT filter's first two buffers: the ring's spans since
    the pipeline began, and the load block."""
    before = {s[5] for _, s in obs_spans.snapshot()}
    pipe = parse_launch(
        f"appsrc name=in caps={VIT_CAPS} ! tensor_filter name=f "
        f'framework=jax model="{VIT}" in-flight=2 ! appsink name=out')
    pipe.start()
    frames = np.random.default_rng(0).integers(
        0, 255, (4, 32, 32, 3), np.uint8, endpoint=True)
    _push(pipe, frames, 1)
    first = pipe["f"].transfer_report()["load"]
    _push(pipe, frames, 2)
    block = pipe["f"].transfer_report()["load"]
    mine = [s for _, s in obs_spans.snapshot() if s[5] not in before
            and s[0] in SPANS]
    _stop(pipe)
    return mine, first, block


def _end(span):
    return span[2] + span[3]


# ------------------------------------------------------------- the spans

def test_the_seven_spans_are_in_the_ring_with_their_nesting(vit_load):
    mine, _, _ = vit_load
    by_name = {}
    for s in mine:
        by_name.setdefault(s[0], []).append(s)
    assert set(by_name) == set(SPANS)
    assert all(s[1] == "load" for s in mine if s[0] != "nns.filter.prepare")
    # the fusion planner opens the framework before start(): two spans,
    # the model and its placement under the first
    starts = sorted(by_name["nns.load.start"], key=lambda s: s[2])
    assert len(starts) == 2
    for name in ("nns.load.model", "nns.load.place"):
        (child,) = by_name[name]
        assert child[6] == starts[0][5]
        assert starts[0][2] <= child[2] and _end(child) <= _end(starts[0])
    (model,), (place,) = by_name["nns.load.model"], by_name["nns.load.place"]
    assert _end(model) <= place[2]
    # one program, its trace and the prepare program under it, in order
    (program,), (trace,), (prep,) = (by_name["nns.load.program"],
                                     by_name["nns.load.trace"],
                                     by_name["nns.filter.prepare"])
    assert trace[6] == prep[6] == program[5]
    assert program[2] <= trace[2] and _end(trace) <= prep[2]
    assert _end(prep) <= _end(program)
    # the first buffer compiled it: recorded after the fact, under the
    # element's start, around the program
    (first,) = by_name["nns.load.first_buffer"]
    assert first[6] == starts[-1][5]
    assert first[2] <= program[2] and _end(program) <= _end(first)
    assert _end(starts[-1]) <= first[2]


def test_the_load_block_has_every_key_and_one_record_a_program(vit_load):
    _, _, block = vit_load
    # flax's init compiles inside zoo.build: the model file's own programs
    assert list(block) == ["start_s", "model_s", "place_s", "model_jit",
                           "first_buffer_s", "total_s", "programs"]
    jit = block["model_jit"]
    assert set(jit) == RECORD_KEYS - {"program", "signature", "donate", "at",
                                      "wall_s", "prepare_s"}
    assert 0 < jit["trace_s"] + jit["lower_s"] + jit["compile_s"] \
        <= block["model_s"] + SLACK
    records = block["programs"]
    assert [r["program"] for r in records] == ["jit_nns_filter_prepare",
                                               "jit_nns_filter_vit"]
    for r in records:
        assert set(r) == RECORD_KEYS
        assert r["at"] == "load" and r["cache"] in ("hit", "miss", "off")
        assert min(r["trace_s"], r["lower_s"], r["compile_s"]) > 0
        assert r["trace_s"] + r["lower_s"] + r["compile_s"] \
            + r["prepare_s"] <= r["wall_s"] + SLACK
        assert r["retrieval_s"] <= r["compile_s"] + SLACK
    prep, main = records
    assert main["signature"] == "uint8[4,32,32,3]" and main["donate"] == []
    assert prep["signature"] == "" and prep["prepare_s"] == 0
    # the prepare program's wall time is what the model's program waited
    assert main["prepare_s"] == prep["wall_s"]
    parts = block["model_s"] + block["place_s"] + sum(
        r["trace_s"] + r["lower_s"] + r["compile_s"] for r in records)
    assert 0 < parts <= block["total_s"] + SLACK
    assert block["model_s"] + block["place_s"] <= block["start_s"]
    assert main["wall_s"] <= block["first_buffer_s"] <= block["total_s"]


def test_a_second_buffer_of_the_same_signature_adds_nothing(vit_load):
    _, first, block = vit_load
    assert first == block


def test_the_trace_is_not_counted_twice():
    """``nns.load.trace`` times the trace around JAX's own event, and a
    nested jit reports inside its caller's: seconds are the union's."""
    assert obs_load.covered([]) == 0
    assert obs_load.covered([(1, 3), (2, 2.5), (1.5, 3)]) == 2
    assert obs_load.covered([(5, 6), (1, 2), (1.5, 2.5)]) == 2.5
    obs_load.install()
    log = obs_load.LoadLog()
    with log.program("jit_test", (((2,), "float32"),), ()):
        with log.trace() as span:
            traced = jax.jit(lambda x: jax.jit(lambda y: y * 2)(x) + 1) \
                .trace(np.ones(2, np.float32))
        traced.lower().compile()
    (record,) = log.programs
    assert 0 < record["trace_s"] <= span.dur_ns / 1e9 + SLACK


# ------------------------------------------------- who is charged for what

def test_a_new_signature_on_the_frame_path_is_a_recompile():
    pipe = _mlp()
    try:
        _push(pipe, np.ones(16, np.float32), 1)
        _push(pipe, np.ones(16, np.float32), 2)
        f = pipe["f"]
        before = f.stats.snapshot()["jit_recompiles"]
        events = len([e for e in RECORDER.events() if e[1] == "recompile"])
        assert [r["at"] for r in f.load_report()["programs"]] == ["load"]
        _push(pipe, np.ones((2, 16), np.float32), 3)
        block = f.load_report()
        assert [(r["at"], r["signature"]) for r in block["programs"]] == [
            ("load", "float32[16]"), ("frame", "float32[2,16]")]
        assert f.stats.snapshot()["jit_recompiles"] == before + 1
        mine = [e for e in RECORDER.events() if e[1] == "recompile"][events:]
        assert [(e[2], e[3]) for e in mine] == [
            ("f", {"program": "jit_nns_filter_mlp",
                   "signature": "float32[2,16]"})]
        assert "nns_events_total" in obs_metrics.render()
        # the frame path's program is no part of the load's phases
        phases = obs_load.phase_seconds(block)
        assert phases["compile"] == block["programs"][0]["compile_s"]
    finally:
        _stop(pipe)


def test_warmup_builds_the_program_before_the_first_buffer():
    pipe = _mlp("warmup=true")
    try:
        f = pipe["f"]
        _push(pipe, np.ones(16, np.float32), 1)
        block = f.load_report()
        (record,) = block["programs"]
        assert record["at"] == "load"
        # the first buffer found its program built
        assert block["first_buffer_s"] < record["wall_s"]
        assert f.stats.snapshot().get("jit_recompiles", 0) == 0
    finally:
        _stop(pipe)


def test_a_jit_outside_any_region_is_charged_to_nobody():
    pipe = _mlp()
    try:
        _push(pipe, np.ones(16, np.float32), 1)
        before = pipe["f"].load_report()
        assert obs_spans.open_account() is None
        jax.jit(lambda x: x * 3 + 1)(np.ones(5, np.float32))
        assert pipe["f"].load_report() == before
    finally:
        _stop(pipe)


def test_a_compile_on_another_thread_is_not_charged_here():
    obs_load.install()
    log = obs_load.LoadLog()

    def compile_one(scale):
        jax.jit(lambda x: x * scale + 2)(np.ones(7, np.float32))

    with log.program("jit_mine", (((7,), "float32"),), ()):
        other = threading.Thread(target=compile_one, args=(5.0,))
        other.start()
        other.join(60)
        assert not other.is_alive()
    with log.program("jit_mine", (((7,), "float32"),), (1,)):
        compile_one(6.0)
    theirs, mine = log.programs
    assert theirs["trace_s"] == theirs["lower_s"] == theirs["compile_s"] == 0
    assert min(mine["trace_s"], mine["lower_s"], mine["compile_s"]) > 0
    assert mine["donate"] == [1] and theirs["wall_s"] > 0


def test_a_program_that_fails_to_build_leaves_no_record():
    log = obs_load.LoadLog()
    with pytest.raises(TypeError):
        with log.program("jit_bad", (), ()):
            raise TypeError("a stale signature")
    assert log.programs == [] and log.report() is None


def test_obs_off_gives_no_block_and_no_error(monkeypatch):
    monkeypatch.setattr(obs_spans, "ENABLED", False)
    pipe = _mlp("in-flight=2")
    try:
        _push(pipe, np.ones(16, np.float32), 1)
        f = pipe["f"]
        assert f.load_report() is None and f.fw.load_report() is None
        report = f.transfer_report()
        assert "load" not in report and report["completed"] == 1
        assert "nns_load_seconds" not in obs_metrics.render()
    finally:
        _stop(pipe)


def test_a_backend_without_the_account_leaves_the_report_alone():
    pipe = parse_launch(
        f"appsrc name=in caps={CAPS16} ! tensor_filter name=f "
        'framework=simlink model=x custom="rtt:1,svc:1" ! appsink name=out')
    pipe.start()
    try:
        _push(pipe, np.ones(16, np.float32), 1)
        assert pipe["f"].load_report() is None
        assert pipe["f"].transfer_report() == {}
    finally:
        _stop(pipe)


def test_metrics_render_the_loads_phases():
    pipe = _mlp()
    pipe.name = "loadphases"
    try:
        _push(pipe, np.ones(16, np.float32), 1)
        block = pipe["f"].load_report()
        samples = obs_metrics.parse(obs_metrics.render())
    finally:
        _stop(pipe)
    got = {dict(lab)["phase"]: v for (name, lab), v in samples.items()
           if name == "nns_load_seconds"
           and dict(lab)["pipeline"] == "loadphases"
           and dict(lab)["element"] == "f"}
    assert set(got) == {"model", "place", "trace", "lower", "compile",
                        "prepare", "first_buffer", "total"}
    assert got["total"] == block["total_s"]
    assert got["model"] == block["model_s"]
    assert got["trace"] == block["programs"][0]["trace_s"]
    assert got["prepare"] == 0


# ---------------------------------------------------- the profiler's trace

def test_the_load_spans_are_in_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        pipe = parse_launch(
            f"appsrc name=in caps={VIT_CAPS} ! tensor_filter name=f "
            f'framework=jax model="{VIT}" in-flight=2 ! appsink name=out')
        pipe.start()
        _push(pipe, np.zeros((4, 32, 32, 3), np.uint8), 1)
        block = pipe["f"].load_report()
        _stop(pipe)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    events.setdefault(ev.name, []).append(
                        (int(ev.duration_ns), dict(ev.stats)))
    assert set(events) == set(SPANS)
    assert all(m["element"] == "f" and m["framework"] == "jax"
               for _, m in events["nns.load.start"])
    (_, model), (_, place) = events["nns.load.model"][0], \
        events["nns.load.place"][0]
    assert model["model"] == VIT and int(model["leaves"]) > 0
    assert int(model["bytes"]) == int(place["bytes"]) > 0
    assert int(place["devices"]) == 1
    (dur, program), = events["nns.load.program"]
    assert dur > 0 and program["program"] == "jit_nns_filter_vit"
    assert program["signature"] == "uint8[4,32,32,3]"
    assert program["at"] == "load"
    (_, trace), = events["nns.load.trace"]
    assert int(trace["equations"]) > 0 and trace["parent"] == program["span"]
    (_, prep), = events["nns.filter.prepare"]
    assert prep["parent"] == program["span"] and int(prep["equations"]) > 0
    # a wait recorded after the fact: a marker that carries its length
    (_, first), = events["nns.load.first_buffer"]
    assert first["element"] == "f"
    assert int(first["dur_ns"]) == round(block["first_buffer_s"] * 1e9)
