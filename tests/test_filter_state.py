"""A state the jax filter keeps on the device between buffers
(``filters/jax_backend.py``): a five-item ``get_model()`` whose program
is ``apply_fn(params, state, *inputs) -> (outputs, state)``. A toy
running sum through real pipelines: stream order under an in-flight
window, donation, the model's own reset, suspend and resume keep the
state, a reload and an invoke error drop it, and a four-item model runs
the program it always ran.
"""
import textwrap
import time

import numpy as np
import pytest

import jax

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.filters import FilterProperties, find_filter
from nnstreamer_tpu.filters.base import (FilterEvent,
                                         HeldStateNotCheckpointable)

CAPS = ("other/tensors,format=static,num_tensors=2,"
        "types=(string)\"float32,int32\",dimensions=(string)\"4,1\","
        "framerate=0/1")

# y = w * (sum of every x since the last buffer whose mark was 0, this
# one included); a negative first entry of x fails the invoke at run time
RUNNING_SUM = textwrap.dedent("""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.tensors.info import TensorsInfo

    def _check(x):
        if float(x[0]) < 0:
            raise ValueError("a negative frame")

    def get_model():
        def apply_fn(p, state, x, mark):
            jax.debug.callback(_check, x)
            total = state["sum"] * (mark[0] != 0) + x
            return (p["w"] * total,), {"sum": total,
                                       "seen": state["seen"] + 1}
        info = TensorsInfo.make("float32,int32", "4,1")
        return (apply_fn, {"w": jnp.full((4,), %(w)s, jnp.float32)}, info,
                TensorsInfo.make("float32", "4"),
                {"sum": np.zeros((4,), np.float32),
                 "seen": np.zeros((), np.int32)})
""")

STATELESS = textwrap.dedent("""
    import jax.numpy as jnp
    from nnstreamer_tpu.tensors.info import TensorsInfo

    def get_model():
        info = TensorsInfo.make("float32,int32", "4,1")
        return (lambda p, x, mark: (p["w"] * x,),
                {"w": jnp.full((4,), 2.0, jnp.float32)}, info,
                TensorsInfo.make("float32", "4"))
""")


def _model(tmp_path, text=RUNNING_SUM, w="1.0", name="sum.py"):
    path = tmp_path / name
    path.write_text(text % {"w": w} if "%(w)s" in text else text)
    return str(path)


def _pipe(model, window="in-flight=4"):
    p = parse_launch(
        f"appsrc name=in caps={CAPS} ! tensor_filter name=f framework=jax "
        f"model={model} {window} ! appsink name=out")
    p.start()
    return p


def _push(p, x, mark):
    p["in"].push_buffer(Buffer.from_arrays(
        [np.full((4,), x, np.float32), np.array([mark], np.int32)]))


def _wait(p, n):
    """Until ``n`` buffers have reached the sink: a push returns before
    the filter has the buffer."""
    deadline = time.monotonic() + 60
    while len(p["out"].buffers) < n and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(p["out"].buffers) >= n


def _drain(p):
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    return [float(np.asarray(b.chunks[0].host())[0]) for b in p["out"].buffers]


@pytest.mark.parametrize("window", ["in-flight=4", "in-flight=1"],
                         ids=["window4", "sync"])
def test_running_sum_in_stream_order_and_reset_at_the_mark(tmp_path, window):
    """Sixteen buffers, a document every five: each output is the sum
    since the last mark of 0, in order, whatever the window's depth."""
    p = _pipe(_model(tmp_path), window)
    want, total = [], 0.0
    for i in range(16):
        mark = i % 5
        total = float(i + 1) + (total if mark else 0.0)
        want.append(total)
        _push(p, i + 1, mark)
    got = _drain(p)
    rep = p["f"].transfer_report()["state"]
    p.stop()
    assert got == want
    assert rep == {"leaves": 2, "bytes": 20, "dispatches": 16, "drops": 0}


def test_the_state_is_donated_where_the_platform_donates(tmp_path,
                                                         monkeypatch):
    """The program is built with the state's leaves donated (arguments
    1 and 2, the parameters never) on a platform that honours donation,
    and with none on one that does not; either way the state's arrays
    are the program's own outputs, never copied through the host."""
    fw_cls = find_filter("jax")
    seen = []
    real = jax.jit

    def jit(fn, **kw):
        seen.append(kw.get("donate_argnums"))
        return real(fn, **kw)

    for platforms, want in ((("cpu",), (1, 2)), (("tpu", "gpu"), None)):
        monkeypatch.setattr(fw_cls, "_DONATION_PLATFORMS", platforms)
        fw = fw_cls()
        fw.open(FilterProperties(framework="jax",
                                 model_files=(_model(tmp_path),)))
        monkeypatch.setattr(jax, "jit", jit)
        x = [np.ones((4,), np.float32), np.array([1], np.int32)]
        first = fw.invoke(x)[0]
        held = list(fw._state)
        second = fw.invoke(x)[0]
        monkeypatch.setattr(jax, "jit", real)
        assert seen.pop(0) == want and not seen
        assert all(isinstance(a, jax.Array) for a in held)
        if want:        # donated to the second call: gone
            assert all(a.is_deleted() for a in held)
        np.testing.assert_array_equal(first, 1.0)
        np.testing.assert_array_equal(second, 2.0)
        fw.close()


def test_suspend_and_resume_keep_the_state(tmp_path):
    p = _pipe(_model(tmp_path))
    _push(p, 1, 0)
    _push(p, 2, 1)
    fw = p["f"].fw
    _wait(p, 2)
    assert fw.handle_event(FilterEvent.SUSPEND)
    assert all(isinstance(x, np.ndarray) for x in fw._state)   # on the host
    _push(p, 3, 2)          # resumes on its own
    assert _drain(p) == [1.0, 3.0, 6.0]
    assert p["f"].transfer_report()["state"]["drops"] == 0
    p.stop()


def test_a_reload_drops_the_state(tmp_path):
    p = _pipe(_model(tmp_path))
    _push(p, 1, 0)
    _push(p, 2, 1)
    _wait(p, 2)
    assert p["f"].reload_model(_model(tmp_path, w="10.0", name="ten.py"))
    _push(p, 3, 2)          # mid-document, but the sum is gone
    assert _drain(p) == [1.0, 3.0, 30.0]
    rep = p["f"].transfer_report()["state"]
    assert rep["drops"] == 1 and rep["leaves"] == 2
    p.stop()


@pytest.mark.parametrize("window", ["in-flight=4", "in-flight=1"],
                         ids=["window4", "sync"])
def test_an_invoke_error_drops_the_state_and_fails_the_buffer(tmp_path,
                                                              window):
    """The third buffer fails at run time: it is counted failed, the
    state it and its successors were built on is dropped once, and the
    stream goes on from the initial state."""
    p = _pipe(_model(tmp_path), window)
    _push(p, 1, 0)
    _push(p, 2, 1)
    _wait(p, 2)
    _push(p, -1, 2)
    deadline = time.monotonic() + 60
    while not p["f"].stats["invoke_errors"] and time.monotonic() < deadline:
        time.sleep(0.005)
    _push(p, 4, 3)
    _push(p, 5, 4)
    got = _drain(p)
    stats, rep = p["f"].stats, p["f"].transfer_report()["state"]
    p.stop()
    assert got == [1.0, 3.0, 4.0, 9.0]
    assert stats["invoke_errors"] == 1 and stats["frames_dropped"] == 1
    assert rep["drops"] == 1


def test_stop_drops_the_state_and_a_restart_begins_anew(tmp_path):
    model = _model(tmp_path)
    p = _pipe(model)
    _push(p, 1, 0)
    _push(p, 2, 1)
    assert _drain(p) == [1.0, 3.0]
    fw = p["f"].fw
    p.stop()
    assert fw._state is None and fw.state_report() is None
    p = _pipe(model)
    _push(p, 5, 1)          # no mark, yet nothing is carried over
    assert _drain(p) == [5.0]
    p.stop()


def test_a_four_item_model_runs_the_program_it_always_ran(tmp_path):
    """No state, no ``state`` block, no marker, and the jit cache's key
    is the stateless one."""
    p = _pipe(_model(tmp_path, STATELESS, name="plain.py"))
    for i in range(4):
        _push(p, i + 1, i)
    assert _drain(p) == [2.0, 4.0, 6.0, 8.0]
    fw = p["f"].fw
    assert "state" not in p["f"].transfer_report()
    assert fw.state_report() is None and fw._state is None
    assert all("state" not in key for key in fw._jit_cache)
    assert p["f"].snapshot_state(str(tmp_path)) is None
    p.stop()


def test_the_cut_takes_the_state_as_an_input_never_as_a_leaf(tmp_path):
    """What the parameter leaves alone determine still runs once per
    load (``filters/prepare.py``), and an equation that reads the state
    never does: the state is an input of the program, after the leaves."""
    text = RUNNING_SUM.replace(
        'return (p["w"] * total,)',
        'return (jnp.exp(p["w"]) * (total + jnp.exp(state["sum"]) * 0),)')
    p = _pipe(_model(tmp_path, text, w="0.0"))
    for i in range(4):
        _push(p, i + 1, i)
    assert _drain(p) == [1.0, 3.0, 6.0, 10.0]
    fw, rep = p["f"].fw, p["f"].transfer_report()
    assert rep["prepared_equations"] == 1            # exp(w), not exp(sum)
    assert fw._cut.sources == (0,) and len(fw._prepared) == 1
    assert rep["state"]["dispatches"] == 4
    p.stop()


def test_a_snapshot_of_a_held_state_is_refused_by_name(tmp_path):
    p = _pipe(_model(tmp_path))
    _push(p, 1, 0)
    _wait(p, 1)
    with pytest.raises(HeldStateNotCheckpointable, match="2 state arrays"):
        p["f"].snapshot_state(str(tmp_path))
    _drain(p)
    p.stop()


def test_the_state_marker_is_recorded_a_dispatch(tmp_path):
    from nnstreamer_tpu.obs import spans
    spans.clear()
    p = _pipe(_model(tmp_path))
    for i in range(3):
        _push(p, 1, i)
    _drain(p)
    p.stop()
    marks = [s for _, s in spans.snapshot() if s[0] == "nns.filter.state"]
    assert len(marks) == 3 and all(m[3] == 0 for m in marks)


def test_a_mesh_refuses_a_model_that_carries_a_state(tmp_path):
    fw = find_filter("jax")()
    with pytest.raises(ValueError, match="carries a state"):
        fw.open(FilterProperties(
            framework="jax", model_files=(_model(tmp_path),),
            custom_properties="mesh:2x1x1"))


def test_a_fused_segment_declines_a_model_that_carries_a_state(tmp_path):
    fw = find_filter("jax")()
    fw.open(FilterProperties(framework="jax",
                             model_files=(_model(tmp_path),)))
    assert fw.traceable_fn() is None
    fw.close()
