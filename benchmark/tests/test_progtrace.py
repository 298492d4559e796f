"""``nnsbench/progtrace.py`` and the readers built on it.

Run with ``python -m pytest benchmark/tests -q`` on the CPU. The
arithmetic (self time, per-program time, scope shares, idle-gap
attribution, rebuilt waits) is held to the numbers written beside the
synthetic trace ``selftest/trace_prog_small.json``; the reader of the
xplane file's bytes to a hand-encoded message; and every new reader is
run on a traced run at rehearsal size, where the CPU's trace has no
device plane and each has to return None and not raise."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from nnsbench import progtrace  # noqa: E402

NEW_READERS = ["queue.wait_ms", "filter.window_wait_ms", "filter.dispatch_ms",
               "transfer.h2d_ms_per_buffer", "transfer.d2h_ms_per_buffer",
               "model_step.device_ms_per_buffer",
               "model_step.attn_device_pct", "model_step.mlp_device_pct",
               "llm.admit_share_pct", "device.idle_attributed_pct"]


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "..", "selftest",
                           "trace_prog_small.json")) as f:
        trace = json.load(f)
    return progtrace.ProgTrace(trace), trace["expect"]


def test_self_time_is_the_span_minus_its_children(small):
    prog, expect = small
    assert prog.window_ns == expect["stretch_ns"]
    for name, want in expect["self_ns"].items():
        assert [prog.self_ns(s) for s in prog.regions(name)] == [want], name


def test_programs_are_found_by_name_inside_the_stretch(small):
    prog, expect = small
    for prefix, want in expect["module_ns"].items():
        assert prog.module_ns(prefix) == want, prefix
    assert prog.module_ns("jit_call") == []      # ran before the stretch


def test_scope_shares(small):
    prog, expect = small
    for part, (num, den) in expect["scope_share"].items():
        assert prog.scope_share(part) == pytest.approx(num / den)
    assert prog.scope_share("block/nothing") is None


def test_idle_gaps_go_to_the_innermost_program_span(small):
    prog, expect = small
    assert prog.idle_gaps() == expect["idle_gaps"]


def test_waits_are_rebuilt_from_their_end_stamp(small):
    prog, expect = small
    for name, want in expect["waits"].items():
        got = sorted([s.lo, s.hi] for s in prog.waits(name))
        assert got == sorted(want), name
    for name, want in expect["busy_ns"].items():
        assert prog.busy_ns(name) == want, name


def test_mean_says_nothing_under_five_spans():
    assert progtrace.mean_ms([1e6] * 4) is None
    assert progtrace.mean_ms([1e6] * 5) == pytest.approx(1.0)


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, payload):
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def test_op_scopes_reads_the_metadata_stat_from_the_files_bytes(tmp_path):
    """A two-plane XSpace encoded by hand: the device plane's event
    metadata carries ``tf_op`` once as a string and once as a reference
    to a stat-metadata name; the host plane is stepped over."""
    def stat_meta(key, name):
        return _field(5, _field(1, key) + _field(2, _field(1, key)
                                                 + _field(2, name)))

    def event_meta(key, name, stat):
        return _field(4, _field(1, key) + _field(2, _field(1, key)
                      + _field(2, name) + _field(5, stat)))

    device = (_field(2, b"/device:TPU:0")
              + _field(3, _field(2, b"XLA Ops") + b"")
              + event_meta(1, b"%fusion.1 = f32[8]", _field(1, 7)
                           + _field(5, b"jit(f)/block/attn/dot:"))
              + event_meta(2, b"%fusion.2 = f32[8]", _field(1, 7)
                           + _field(7, 9))
              + event_meta(3, b"%copy.3 = f32[8]", _field(1, 8)
                           + _field(5, b"not a scope"))
              + stat_meta(7, b"tf_op") + stat_meta(8, b"hlo_category")
              + stat_meta(9, b"jit(f)/block/mlp/dot:"))
    host = _field(2, b"/host:CPU") + event_meta(
        1, b"nns.queue.wait", _field(1, 7) + _field(5, b"x"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, host) + _field(1, device))
    assert progtrace.op_scopes(str(path)) == {
        "%fusion.1 = f32[8]": "jit(f)/block/attn/dot:",
        "%fusion.2 = f32[8]": "jit(f)/block/mlp/dot:"}


@pytest.fixture(scope="module")
def traced_rehearsal():
    """One traced run of the stream cell at rehearsal size, its trace
    kept until the tests have looked at it."""
    import shutil
    cell = "vit_h14.stream_b32"
    res = bench_run.run_cell(cell, 2_500_000_019, 1.5, 1, rehearsal=True,
                             keep_trace=True)
    trace_dir = os.path.join(bench_run.OUT_DIR, "trace-" + cell)
    try:
        yield res, trace_dir
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def test_the_programs_spans_are_in_the_profilers_trace(traced_rehearsal):
    res, trace_dir = traced_rehearsal
    assert res["correct"] and res["failed"] == 0
    prog = progtrace.ProgTrace(progtrace.load(trace_dir))
    names = {s.name for s in prog.spans}
    assert {"nns.queue.wait", "nns.filter.window_wait",
            "nns.filter.dispatch", "nns.filter.complete",
            "nns.transfer.upload", "nns.transfer.fetch",
            "bench.push"} <= names
    assert progtrace.mean_ms(
        s.hi - s.lo for s in prog.waits("nns.queue.wait")) is not None
    assert not prog.has_device


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_returns_none_without_a_device_plane(name, traced_rehearsal,
                                                    capsys):
    res, trace_dir = traced_rehearsal
    read = bench_run.load_reader(name)
    # what run.py gives a reader when the trace could not be reduced
    assert read({"trace": None}) is None
    assert name not in res["metrics"]
    # the CPU's own trace holds the program's spans on host threads but
    # no device plane to lay them beside: nothing is read, and it says so
    progtrace._of_dir.cache_clear()
    assert progtrace._of_dir(trace_dir) is None
    assert "no device plane" in capsys.readouterr().err
