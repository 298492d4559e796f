"""The bridge from ``obs/spans.py`` into the ``jax.profiler`` trace
(ISSUE 26): ``region`` / ``record_span(prof=)``, the spans that were
missing or wrong (appsrc's entry wait, the window wait, a dispatch span
with a real extent), stable names for the jitted programs, and the
benchmark's ``progtrace`` arithmetic on its recorded synthetic trace.

The profiler tests take a real ``jax.profiler`` trace on the CPU and
read the xplane back with ``ProfileData``: what a reader of a chip
trace finds on the host planes is exactly this.
"""
import glob
import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.obs import context as obs_ctx
from nnstreamer_tpu.obs import metrics as obs_metrics
from nnstreamer_tpu.obs import spans as obs_spans

REPO = Path(__file__).resolve().parent.parent

CAPS16 = ('"other/tensors,format=static,num_tensors=1,'
          'types=(string)float32,dimensions=(string)16,'
          'framerate=(fraction)0/1"')
MLP = '"zoo://mlp?in_dim=16&hidden=32&out_dim=4&dtype=float32"'
FRAMES = 6


def _mine(sids):
    """Ring spans by id, for the ids a test made itself."""
    return {s[5]: s for _, s in obs_spans.snapshot() if s[5] in sids}


# ------------------------------------------------------------ the ring

def test_region_nests_and_parents_on_the_open_region():
    with obs_spans.region("nns.test.outer", "test") as outer:
        with obs_spans.region("nns.test.inner", "test") as inner:
            time.sleep(0.001)
    got = _mine({outer.sid, inner.sid})
    o, i = got[outer.sid], got[inner.sid]
    assert (o[0], o[1], o[6]) == ("nns.test.outer", "test", 0)
    assert i[6] == outer.sid                     # parent: the open region
    assert o[2] <= i[2] and i[2] + i[3] <= o[2] + o[3]   # nested extent
    assert inner.dur_ns == i[3] >= 1_000_000


def test_region_parents_on_the_context_and_advances_it():
    ctx = obs_ctx.TraceContext(obs_ctx.next_id(), 41, time.time_ns())
    with obs_spans.region("nns.test.work", "test", ctx,
                          name="f:work") as work:
        # a child without a context joins the frame's trace
        with obs_spans.region("nns.test.child", "test") as child:
            pass
    got = _mine({work.sid, child.sid})
    assert got[work.sid][0] == "f:work"          # the ring's own name
    assert got[work.sid][4] == ctx.trace_id and got[work.sid][6] == 41
    assert got[child.sid][4] == ctx.trace_id
    assert got[child.sid][6] == work.sid
    assert ctx.span_id == work.sid               # the chain moved on


def test_window_wait_is_a_span_of_the_frame_and_queue_time():
    from nnstreamer_tpu.tensors.transfer import InFlightWindow
    win = InFlightWindow(1)
    ctx = obs_ctx.TraceContext(obs_ctx.next_id(), 0, time.time_ns())
    t = win.acquire(ctx=ctx, element="f")
    assert ctx.q_ns >= 0
    span = _mine({ctx.span_id})[ctx.span_id]
    assert span[:2] == ("f:window_wait", "queue") and span[3] == ctx.q_ns
    win.release(t)
    assert win.acquire() is not None             # no context: no span


def test_obs_off_records_nothing_and_builds_no_annotation(monkeypatch):
    built = []
    monkeypatch.setattr(obs_spans, "_annotation",
                        lambda *a, **k: built.append(a))
    monkeypatch.setattr(obs_spans, "ENABLED", False)
    before = len(obs_spans.snapshot())
    with obs_spans.region("nns.test.off", "test") as r:
        pass
    assert r.dur_ns == 0
    assert obs_spans.record_span("x", "test", 0, 1,
                                 prof="nns.test.wait") == 0
    assert len(obs_spans.snapshot()) == before and built == []


def test_named_program_names_the_module_and_leaves_fn_alone():
    import jax

    def fn(x):
        return x + 1

    prog = obs_spans.named_program("nns_fused_seg-0.a", fn)
    assert prog.__name__ == "nns_fused_seg_0_a" and fn.__name__ == "fn"
    text = jax.jit(prog).lower(np.ones(2, np.float32)).as_text()
    assert "module @jit_nns_fused_seg_0_a" in text


# ---------------------------------------------------- the profiler's trace

def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nns."):
                    out.append((ev.name, int(ev.duration_ns),
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """``appsrc ! queue ! tensor_filter in-flight=2 ! appsink`` under a
    ``jax.profiler`` trace on the CPU; the xplane's ``nns.*`` events."""
    import jax
    pipe = parse_launch(
        f"appsrc name=in caps={CAPS16} ! queue name=q0 "
        f"! tensor_filter name=f framework=jax model={MLP} in-flight=2 "
        "prefetch-host=true ! appsink name=out")
    pipe.start()
    # compile outside the trace
    pipe["in"].push_buffer(Buffer.from_arrays([np.ones(16, np.float32)]))
    deadline = time.monotonic() + 60
    while not pipe["out"].buffers and time.monotonic() < deadline:
        time.sleep(0.01)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for i in range(FRAMES):
            pipe["in"].push_buffer(Buffer.from_arrays(
                [np.full(16, i, np.float32)], pts=i + 1))
        while len(pipe["out"].buffers) < FRAMES + 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        for b in pipe["out"].buffers:
            b.chunks[0].host()
    finally:
        jax.profiler.stop_trace()
    fw = pipe["f"].fw
    module = next(iter(fw._jit_cache.values())).lower(
        fw._params, jax.ShapeDtypeStruct((16,), np.float32)).as_text()
    pipe["in"].end_stream()
    pipe.stop()
    assert len(pipe["out"].buffers) == FRAMES + 1
    traces = {obs_ctx.ctx_of(b).trace_id for b in pipe["out"].buffers[1:]}
    return _host_events(trace_dir), traces, module


@pytest.mark.parametrize("name", ["nns.queue.wait", "nns.filter.window_wait",
                                  "nns.filter.dispatch",
                                  "nns.filter.complete"])
def test_every_frame_leaves_the_span_in_the_xplane(traced, name):
    events, traces, _ = traced
    mine = [e for e in events if e[0] == name and e[2]["trace"] in traces]
    # one trace= per frame, shared with the ring's trace id
    assert {e[2]["trace"] for e in mine} == traces
    per_frame = 2 if name == "nns.queue.wait" else 1   # appsrc + q0
    assert len(mine) == per_frame * FRAMES
    for _, _, meta in mine:
        assert meta["span"] and "parent" in meta and meta["element"]


def test_waits_are_markers_with_dur_ns_and_regions_have_extent(traced):
    events, traces, _ = traced
    for name, dur, meta in events:
        if meta.get("trace") not in traces:
            continue
        if name in ("nns.queue.wait", "nns.filter.window_wait"):
            assert int(meta["dur_ns"]) >= 0
        elif name in ("nns.filter.dispatch", "nns.filter.complete"):
            assert "dur_ns" not in meta
            assert dur > 0, f"{name} has no extent"
    assert {e[2]["element"] for e in events
            if e[0] == "nns.queue.wait"} == {"in", "q0"}


def test_transfers_are_in_the_xplane_with_their_bytes(traced):
    events, _, _ = traced
    up = [e for e in events if e[0] == "nns.transfer.upload"]
    down = [e for e in events if e[0] == "nns.transfer.fetch"]
    assert len(up) >= FRAMES and down
    assert all(int(e[2]["bytes"]) == 64 and int(e[2]["arrays"]) == 1
               for e in up)
    # the staging put is the dispatch span's child, in the frame's trace
    dispatch = {e[2]["span"]: e[2]["trace"] for e in events
                if e[0] == "nns.filter.dispatch"}
    assert all(dispatch.get(e[2]["parent"]) == e[2]["trace"] for e in up)


def test_the_jax_filters_program_is_named_after_its_model(traced):
    _, _, module = traced
    assert "module @jit_nns_filter_mlp" in module


# ------------------------------------------------------------- the program

def test_appsrc_entry_wait_is_inside_the_e2e_latency():
    obs_metrics.reset()
    pipe = parse_launch(f"appsrc name=in caps={CAPS16} ! appsink name=out")
    pipe.name = "entrywait"
    # the src loop is not running yet: the buffer waits in the entry
    pipe["in"].push_buffer(Buffer.from_arrays([np.ones(16, np.float32)]))
    time.sleep(0.25)
    pipe.start()
    pipe["in"].end_stream()
    pipe.wait_eos(timeout=30)
    pipe.stop()
    ctx = obs_ctx.ctx_of(pipe["out"].buffers[0])
    assert ctx.q_ns >= 200_000_000
    samples = obs_metrics.parse(obs_metrics.render())
    total = sum(v for (n, lab), v in samples.items()
                if n == "nns_e2e_latency_seconds_sum"
                and dict(lab).get("sink") == "out")
    assert total >= 0.2


def test_appsrc_max_buffers_from_a_launch_string():
    pipe = parse_launch(
        f"appsrc name=in max-buffers=3 caps={CAPS16} ! appsink name=out")
    assert pipe["in"]._q.maxsize == 3
    pipe["in"].set_property("max-buffers", 5)
    assert pipe["in"]._q.maxsize == 5
    pipe.start()
    try:
        with pytest.raises(RuntimeError):
            pipe["in"].set_property("max-buffers", 1)
    finally:
        pipe["in"].end_stream()
        pipe.stop()


LLM_PROGRAMS = {"_prefill": "nns_llm_prefill", "_decode": "nns_llm_decode",
                "_decode_multi": "nns_llm_decode_multi",
                "_insert": "nns_llm_cache_insert",
                "_decode_paged": "nns_llm_decode_paged",
                "_pool_insert": "nns_llm_pool_insert",
                "_pool_gather": "nns_llm_pool_gather",
                "_prefill_past": "nns_llm_prefill_past"}


def test_the_llm_programs_carry_their_names():
    import jax.numpy as jnp
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    fw = find_filter("llm")()
    fw.open(FilterProperties(
        model_files=("zoo://gpt?vocab=64&d_model=32&n_heads=2&n_layers=1",),
        custom_properties="max_tokens:2,n_parallel:2,paged:true,"
                          "pool_blocks:8,max_len:32,chunk:2"))
    try:
        for attr, name in LLM_PROGRAMS.items():
            assert getattr(fw, attr).__wrapped__.__name__ == name, attr
        assert fw._chunk_fn(2, 0.0).__wrapped__.__name__ == "nns_llm_chunk"
        assert fw._chunk_fn_paged(2, 0.0).__wrapped__.__name__ == \
            "nns_llm_chunk_paged"
        cache = fw._tfm.init_cache(fw._cfg, batch=1, max_len=8)
        text = fw._prefill.lower(
            fw._params, cache, jnp.zeros((1, 8), jnp.int32),
            jnp.asarray(3, jnp.int32)).as_text()
        assert "module @jit_nns_llm_prefill" in text
    finally:
        fw.close()


def test_the_llm_schedulers_thread_records_its_spans():
    """admit (as the ring's ``llm-prefill``) with prefill and kv_copy
    under it, then chunk / fetch / emit, all on the scheduler thread."""
    caps = ('"other/tensors,format=flexible,num_tensors=1,'
            'types=(string)int32,framerate=(fraction)0/1"')
    pipe = parse_launch(
        f"appsrc name=in caps={caps} ! tensor_filter name=f framework=llm "
        'model="zoo://gpt?vocab=64&d_model=32&n_heads=2&n_layers=1" '
        'invoke-async=true invoke-dynamic=true custom="max_tokens:4,'
        'n_parallel:2,paged:true,pool_blocks:8,max_len:32,chunk:2" '
        "! appsink name=out")
    pipe.start()
    pipe["in"].push_buffer(Buffer.from_arrays(
        [np.array([1, 2, 3], np.int32)], pts=7))
    deadline = time.monotonic() + 120
    while len(pipe["out"].buffers) < 4 and time.monotonic() < deadline:
        time.sleep(0.02)
    pipe["in"].end_stream()
    pipe.stop()
    assert len(pipe["out"].buffers) == 4
    sched = [tid for tid, name in obs_spans.thread_names().items()
             if name == "llm-sched"]
    spans = [s for tid, s in obs_spans.snapshot() if tid in sched]
    names = {s[0] for s in spans}
    assert {"llm-prefill", "nns.llm.prefill", "nns.llm.kv_copy",
            "nns.llm.chunk", "nns.llm.fetch", "nns.llm.emit"} <= names
    admit = [s for s in spans if s[0] == "llm-prefill"][-1]
    kids = {s[0] for s in spans if s[6] == admit[5]}
    assert {"nns.llm.prefill", "nns.llm.kv_copy"} <= kids


def test_device_memory_gauges(monkeypatch):
    import jax

    class Dev:
        platform, id = "tpu", 0

        def memory_stats(self):
            return {"bytes_in_use": 10, "peak_bytes_in_use": 30,
                    "bytes_limit": 100, "num_allocs": 5}

    # the CPU backend reports none: the family is absent
    assert "nns_device_memory_bytes" not in obs_metrics.render()
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    samples = obs_metrics.parse(obs_metrics.render())
    got = {dict(lab)["kind"]: v for (n, lab), v in samples.items()
           if n == "nns_device_memory_bytes"
           and dict(lab)["device"] == "tpu:0"}
    assert got == {"in_use": 10.0, "peak": 30.0, "limit": 100.0}


# ------------------------------------------------ the benchmark's readers

def test_progtrace_on_its_recorded_synthetic_trace():
    sys.path.insert(0, str(REPO / "benchmark"))
    try:
        from nnsbench import progtrace
    finally:
        sys.path.remove(str(REPO / "benchmark"))
    with open(REPO / "benchmark" / "selftest"
              / "trace_prog_small.json") as f:
        trace = json.load(f)
    prog, want = progtrace.ProgTrace(trace), trace["expect"]
    for name, ns in want["self_ns"].items():
        assert [prog.self_ns(s) for s in prog.regions(name)] == [ns]
    for prefix, ns in want["module_ns"].items():
        assert prog.module_ns(prefix) == ns
    assert prog.idle_gaps() == want["idle_gaps"]
    for part, (num, den) in want["scope_share"].items():
        assert prog.scope_share(part) == pytest.approx(num / den)
