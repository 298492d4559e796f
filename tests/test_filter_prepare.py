"""The jax filter runs what the parameter leaves alone determine once
per load (filters/prepare.py): the equations of the traced program
whose operands are leaves, literals or results of such equations, a
leaf's conversion to its compute dtype among them. Same bits out, one
shared set of results, redone whenever the parameters are replaced, and
nothing at all where no equation qualifies.
"""
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.filters import FilterProperties, find_filter, prepare
from nnstreamer_tpu.filters.base import FilterEvent
from nnstreamer_tpu.obs.spans import named_program

VIT = "zoo://vit?size=32&patch=8&d_model=64&layers=2&heads=4&classes=10"
VIT_CAPS = ("other/tensors,format=static,num_tensors=1,"
            "types=(string)uint8,dimensions=(string)3:32:32:4,framerate=0/1")


def _open(model, custom=""):
    fw = find_filter("jax")()
    fw.open(FilterProperties(framework="jax", model_files=(model,),
                             custom_properties=custom))
    return fw


def _frames(batch, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (batch, 32, 32, 3), np.uint8, endpoint=True)


@pytest.fixture
def conversions(monkeypatch):
    """Counts the runs of the load's program, by their source leaves."""
    calls = []
    real = prepare.load

    def load(cut, leaves):
        calls.append(len(cut.sources))
        return real(cut, leaves)

    monkeypatch.setattr(prepare, "load", load)
    return calls


def _nothing_to_the_load(monkeypatch):
    real = prepare.split
    monkeypatch.setattr(prepare, "split", lambda closed, n: real(closed, 0))


def _old_narrowable(closed, n_leaves):
    """PR 27's rule, kept as the oracle of the one-equation case:
    ``{leaf index: dtype}`` of the leaves whose every use is a plain
    ``convert_element_type`` to one narrower floating dtype."""
    from jax.extend.core import Var
    jaxpr = closed.jaxpr
    index = {v: i for i, v in enumerate(jaxpr.invars[:n_leaves])}
    target = {}
    for eqn in jaxpr.eqns:
        dtype = None
        if eqn.primitive is jax.lax.convert_element_type_p \
                and not eqn.params["weak_type"] \
                and eqn.params["sharding"] is None:
            dtype = eqn.params["new_dtype"]
        for v in eqn.invars:
            i = index.get(v) if isinstance(v, Var) else None
            if i is not None:
                target[i] = dtype if target.get(i, dtype) == dtype else None
    for v in jaxpr.outvars:
        if isinstance(v, Var) and v in index:
            target[index[v]] = None
    out = {}
    for i, dtype in target.items():
        src = jaxpr.invars[i].aval.dtype
        if dtype is not None and jnp.issubdtype(src, jnp.floating) \
                and jnp.issubdtype(dtype, jnp.floating) \
                and jnp.dtype(dtype).itemsize < src.itemsize:
            out[i] = jnp.dtype(dtype)
    return out


def _model_file(tmp_path, body):
    path = tmp_path / "model.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


# a float32 tree in which `k`'s every use is the conversion, and `w` is
# also read in float32 together with an input
TWO_USES = """
    import jax.numpy as jnp
    import numpy as np
    from nnstreamer_tpu.tensors.info import TensorsInfo

    def get_model():
        rng = np.random.default_rng(0)
        params = {"k": rng.standard_normal((16, 8)).astype(np.float32),
                  "w": rng.standard_normal((8, 8)).astype(np.float32)}

        def apply_fn(p, x):
            h = x.astype(jnp.bfloat16) @ p["k"].astype(jnp.bfloat16)
            h = h @ p["w"].astype(jnp.bfloat16)
            return h.astype(jnp.float32) @ p["w"]

        return (apply_fn, params, TensorsInfo.make("float32", "16"),
                TensorsInfo.make("float32", "8"))
"""

# names the `gpt` rule table shards over the model axis
SHARDED = """
    import jax.numpy as jnp
    import numpy as np
    from nnstreamer_tpu.tensors.info import TensorsInfo

    def get_model():
        rng = np.random.default_rng(0)
        params = {"w1": rng.standard_normal((64, 128)).astype(np.float32),
                  "w2": rng.standard_normal((128, 16)).astype(np.float32),
                  "scale": np.ones((16,), np.float32)}

        def apply_fn(p, x):
            h = x.astype(jnp.bfloat16) @ p["w1"].astype(jnp.bfloat16)
            h = h @ p["w2"].astype(jnp.bfloat16)
            return h.astype(jnp.float32) * p["scale"]

        return (apply_fn, params, TensorsInfo.make("float32", "64"),
                TensorsInfo.make("float32", "16"))
"""


@pytest.mark.parametrize("window", ["", "in-flight=2 prefetch-host=true"],
                         ids=["sync", "windowed"])
def test_pipeline_logits_bit_identical(monkeypatch, window):
    """(a) a float32-leaves model through a real pipeline: the same
    bytes with the leaves converted once and with the leaves left alone,
    and the copy is the kernels' half."""
    def run():
        p = parse_launch(
            f'appsrc name=in caps="{VIT_CAPS}" ! tensor_filter name=f '
            f'framework=jax model={VIT} {window} ! appsink name=out')
        p.start()
        for i in range(3):
            p["in"].push_buffer(Buffer.from_arrays([_frames(4, i)]))
        p["in"].end_stream()
        assert p.wait_eos(timeout=120)
        rep = p["f"].transfer_report()
        kernels = sum(x.nbytes for path, x in
                      jax.tree_util.tree_leaves_with_path(p["f"].fw._params)
                      if "kernel" in jax.tree_util.keystr(path)
                      and "EncoderBlock" in jax.tree_util.keystr(path))
        out = [np.asarray(b.chunks[0].host()).tobytes()
               for b in p["out"].buffers]
        p.stop()
        return out, rep, kernels

    got, rep, kernels = run()
    assert rep["prepared_leaves"] > 0
    assert rep["prepared_bytes"] > 0.95 * kernels / 2
    assert rep["prepared_equations"] == rep["prepared_leaves"]
    _nothing_to_the_load(monkeypatch)
    want, rep0, _ = run()
    assert rep0.get("prepared_leaves", 0) == 0
    assert rep0.get("prepared_bytes", 0) == 0
    assert rep0.get("prepared_equations", 0) == 0
    assert len(got) == 3 and got == want


@pytest.mark.parametrize("model,shape", [
    ("zoo://mlp?dtype=float32", (4, 64)),     # used in float32
    ("zoo://mlp", (4, 64)),                   # already bfloat16
    ("zoo://toyseg", (8, 8)),                 # no conversion at all
])
def test_nothing_to_convert_same_program_same_arrays(conversions, model,
                                                     shape):
    """(b) no leaf qualifies: the program is ``jax.jit`` of ``apply_fn``
    as before and it is handed the very arrays that were loaded."""
    fw = _open(model)
    loaded = jax.tree.leaves(fw._params)
    x = np.random.default_rng(0).random(shape, np.float32)
    fw.invoke([x])
    assert fw.prepared_report() == {"prepared_leaves": 0,
                                    "prepared_bytes": 0,
                                    "prepared_equations": 0,
                                    "kernel_calls": {}}
    assert conversions == [] and fw._prepared is None
    exe, = fw._jit_cache.values()
    assert not fw._on_prepared
    assert all(a is b for a, b in zip(jax.tree.leaves(fw._params), loaded))
    plain = jax.jit(named_program("nns_filter_" + fw._model_stem, fw._apply))
    assert exe.lower(fw._params, x).as_text() \
        == plain.lower(fw._params, x).as_text()
    fw.close()


@pytest.mark.parametrize("model,custom", [
    (VIT, ""), (VIT, "mesh:8x1x1"),
    ("zoo://mlp?dtype=float32", ""),
    ("zoo://mlp?dtype=float32", "mesh:4x1x2,rules:gpt"),
], ids=["narrowed", "narrowed-mesh", "plain", "plain-mesh"])
def test_one_python_trace_of_the_model_per_program(model, custom):
    """Reading the leaf set costs no second run of the model's Python:
    the narrowed program is built from the jaxpr, the plain one is the
    ``jax.jit`` that was traced."""
    fw = _open(model, custom)
    runs = []
    apply_fn = fw._apply

    def counting(p, *xs):
        runs.append(1)
        return apply_fn(p, *xs)

    fw._apply = counting
    x = _frames(8) if model is VIT \
        else np.random.default_rng(0).random((8, 64), np.float32)
    fw.invoke([x])
    fw.invoke([x])
    jax.block_until_ready(fw.dispatch([x]))
    assert len(runs) == 1 and len(fw._jit_cache) == 1
    fw.close()


def test_leaf_with_a_float32_use_stays_beside_its_conversion(tmp_path):
    """(c) a leaf that is also read in float32 with an input stays what
    the step reads, the very array that was loaded; its conversion runs
    at the load all the same, and only ``k``, which the step no longer
    reads, counts as held a second time."""
    fw = _open(_model_file(tmp_path, TWO_USES))
    x = np.random.default_rng(1).random((4, 16), np.float32)
    got = np.asarray(fw.invoke([x])[0])
    cut = fw._cut
    assert cut.sources == (0, 1) and cut.kept == (1,)      # k, w | w
    assert cut.narrowed == {0: 16 * 8 * 2}
    w, k16, w16 = fw._prepared
    assert w is fw._params["w"]
    assert k16.dtype == w16.dtype == jnp.bfloat16
    assert k16.shape == (16, 8) and w16.shape == (8, 8)
    assert fw.prepared_report() == {"prepared_leaves": 1,
                                    "prepared_bytes": 16 * 8 * 2,
                                    "prepared_equations": 2,
                                    "kernel_calls": {}}
    want = np.asarray(jax.jit(fw._apply)(fw._params, x))
    assert got.tobytes() == want.tobytes()
    fw.close()


def _rejects(use):
    def f(w, x):
        if use == "returned":
            return x @ w.astype(jnp.bfloat16), w
        if use == "sub_program":
            return jax.jit(lambda a: a.astype(jnp.bfloat16))(w) @ x
        if use == "two_dtypes":
            return x @ w.astype(jnp.bfloat16) \
                + (x @ w.astype(jnp.float16)).astype(jnp.bfloat16)
        return x.astype(w.dtype) @ w.astype(
            jnp.float32 if use == "wider" else jnp.int8)

    w = jnp.ones((4, 4), jnp.bfloat16 if use == "wider" else jnp.float32)
    return jax.make_jaxpr(f)(w, jnp.ones((4, 4), jnp.bfloat16))


@pytest.mark.parametrize("use,equations,kept,narrowed", [
    ("returned", 1, (0,), {}),        # converted once, and still returned
    ("sub_program", 0, (0,), {}),     # a jit's operand: not looked into
    ("two_dtypes", 2, (), {0: 64}),   # both copies are held
    ("wider", 1, (), {}),             # moved, but no narrower copy
    ("integer", 1, (), {}),
])
def test_split_on_one_leaf(use, equations, kept, narrowed):
    """The rule itself on PR 27's cases: what goes to the load, whether
    the step still reads the leaf, and what counts as a leaf held a
    second time in a narrower floating dtype."""
    cut = prepare.split(_rejects(use), 1)
    assert (cut.equations, cut.kept, cut.narrowed) \
        == (equations, kept, narrowed)
    assert bool(cut) == bool(equations)
    if use != "two_dtypes":     # the oracle refuses what now holds both
        assert set(cut.narrowed) == set(_old_narrowable(_rejects(use), 1))


def _relaid(p, x):
    """A toy whose leaves are cut, padded, transposed and reshaped
    before use, as ``models/latent.py`` re-lays its projections."""
    w = p["w"].reshape(16, 4, 6)                       # [r, h, n]
    key = jax.lax.pad(w[..., :4], np.zeros((), w.dtype),
                      [(0, 0, 0), (0, 0, 0), (0, 4, 0)])
    key = jnp.transpose(key, (1, 2, 0)).reshape(32, 16)
    val = jnp.transpose(w[..., 4:], (1, 2, 0))
    h = jnp.einsum("sr,dr->sd", x, key) * p["scale"]   # a view's reader
    v = jnp.einsum("sr,hdr->shd", x, val)

    def body(c, row):                   # the leaf inside a loop's body
        return c + row @ p["inside"].T, ()

    looped, _ = jax.lax.scan(body, jnp.zeros((16,), x.dtype), x)
    gate = jax.lax.cond(x[0, 0] > 0, lambda: p["branch"] * 2.0,
                        lambda: p["branch"])
    return h + (x @ p["mixed"]), v + gate, looped, p["returned"]


def _relaid_params():
    rng = np.random.default_rng(0)
    shapes = {"w": (16, 24), "scale": (32,), "inside": (16, 16),
              "branch": (2,), "mixed": (16, 32), "returned": (3,)}
    return {k: rng.standard_normal(s).astype(np.float32)
            for k, s in shapes.items()}


def test_split_moves_what_the_leaves_alone_determine():
    """Pads, slices, transposes and the reshapes between them go to the
    load; a view at the end of a chain, a leaf read with an input, a
    returned leaf and whatever a ``scan`` / ``cond`` body reads stay."""
    params = _relaid_params()
    x = np.random.default_rng(1).standard_normal((8, 16)).astype(np.float32)
    jitted = jax.jit(_relaid)
    closed, out_tree, cut = prepare.trace(jitted, params, [x])
    names = sorted(params)          # the flat order of a dict's leaves
    moved = [e.primitive.name for e in cut.load.eqns]
    assert sorted(moved) == ["pad", "reshape", "slice", "slice",
                             "transpose", "transpose"]
    assert [names[i] for i in cut.sources] == ["w"]
    assert [names[i] for i in cut.kept] == [
        "branch", "inside", "mixed", "returned", "scale"]
    assert cut.narrowed == {} and cut.equations == 6
    # the step reads the two re-laid arrays, and re-views the first
    assert [v.aval.shape for v in cut.load.outvars] == [(4, 8, 16),
                                                        (4, 2, 16)]
    left = {e.primitive.name for e in cut.step.eqns}
    assert "pad" not in left and {"scan", "cond", "reshape"} <= left
    assert len(cut.step.eqns) == len(closed.jaxpr.eqns) - 6
    leaves = jax.tree.leaves(params)
    held = [leaves[i] for i in cut.kept] + prepare.load(cut, leaves)
    got = jax.jit(prepare.program(closed, out_tree, cut))(held, x)
    want = jitted(params, x)
    assert all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("batch", [1, 4])
def test_vit_shaped_trace_moves_exactly_its_conversions(batch):
    """For a flax module the pass finds what PR 27's rule found: the
    leaves only ever read in bfloat16, their conversions and nothing
    else (the reshape after a converted bias is a view and stays)."""
    fw = _open(VIT)
    closed, _, cut = prepare.trace(jax.jit(fw._apply), fw._params,
                                   [_frames(batch)])
    n = len(jax.tree.leaves(fw._params))
    old = _old_narrowable(closed, n)
    assert old and set(cut.narrowed) == set(old) == set(cut.sources)
    leaves = jax.tree.leaves(fw._params)
    assert cut.narrowed == {i: leaves[i].size * d.itemsize
                            for i, d in old.items()}
    assert cut.equations == len(old)
    assert {e.primitive.name for e in cut.load.eqns} \
        == {"convert_element_type"}
    assert not set(cut.kept) & set(cut.sources)
    fw.close()


def test_signatures_share_one_converted_tree(conversions):
    """(d) the set is the model's, not the input shape's."""
    fw = _open(VIT)
    fw.invoke([_frames(1)])
    tree = fw._prepared
    held = list(tree)
    out4 = np.asarray(fw.invoke([_frames(4)])[0])
    assert len(fw._jit_cache) == 2
    assert fw._on_prepared == set(fw._jit_cache)
    assert fw._prepared is tree
    assert all(a is b for a, b in zip(fw._prepared, held))
    assert conversions == [fw.prepared_report()["prepared_leaves"]]
    want = np.asarray(jax.jit(fw._apply)(fw._params, _frames(4)))
    assert out4.tobytes() == want.tobytes()
    fw.close()


def test_signature_that_disagrees_runs_on_loaded_leaves(monkeypatch,
                                                        conversions):
    """A later program whose trace is cut otherwise must not be handed
    results it would not read, nor miss a leaf it reads in float32."""
    fw = _open(VIT)
    fw.invoke([_frames(1)])
    real = prepare.split
    # the last leaf passes for an input: its conversion stays
    monkeypatch.setattr(prepare, "split",
                        lambda closed, n: real(closed, n - 1))
    got = np.asarray(fw.invoke([_frames(4)])[0])
    sig1, = fw._on_prepared
    assert len(fw._jit_cache) == 2 and sig1[0][0][0] == 1
    assert len(conversions) == 1
    want = np.asarray(jax.jit(fw._apply)(fw._params, _frames(4)))
    assert got.tobytes() == want.tobytes()
    fw.close()


@pytest.mark.parametrize("how", ["reload", "suspend"])
def test_replaced_parameters_are_converted_again(conversions, how):
    """(e) a reload serves the new weights and a resume the reloaded
    tree: never the copy made from the parameters that went."""
    fw = _open(VIT)
    x = _frames(4)
    first = np.asarray(fw.invoke([x])[0])
    old = fw._prepared
    if how == "reload":
        assert fw.handle_event(FilterEvent.RELOAD_MODEL,
                               {"model_files": (VIT + "&seed=1",)})
        ref = _open(VIT + "&seed=1")
        want = np.asarray(jax.jit(ref._apply)(ref._params, x))
        ref.close()
        assert want.tobytes() != first.tobytes()
    else:
        assert fw.handle_event(FilterEvent.SUSPEND)
        want = first
    assert fw._prepared is None and fw._jit_cache == {}
    assert fw._on_prepared == set()
    assert fw.prepared_report()["prepared_bytes"] == 0
    got = np.asarray(fw.invoke([x])[0])
    assert got.tobytes() == want.tobytes()
    assert fw._prepared is not old and len(conversions) == 2
    assert fw.prepared_report()["prepared_leaves"] == conversions[0]
    fw.close()
    assert fw._prepared is None and fw._cut is None


def test_mesh_converted_leaves_keep_their_sharding(tmp_path):
    """(f) on the 8-device mesh a converted leaf lies where its source
    leaf lies, and the sharded program reads the converted tree."""
    model = _model_file(tmp_path, SHARDED)
    x = np.random.default_rng(2).random((8, 64), np.float32)
    one = _open(model)
    want = np.asarray(one.invoke([x])[0])
    one.close()
    fw = _open(model, "mesh:4x1x2,rules:gpt")
    out = fw.invoke([x])[0]
    assert len(out.sharding.device_set) == 8
    assert fw.prepared_report()["prepared_leaves"] == 2
    scale, *converted = fw._prepared          # the kept leaf, the results
    for name, conv in zip(("w1", "w2"), converted):
        src = fw._params[name]
        assert conv.dtype == jnp.bfloat16 and conv.shape == src.shape
        assert conv.sharding == src.sharding
        assert not src.sharding.is_fully_replicated
    assert scale is fw._params["scale"]
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-2, atol=2e-2)
    fw.close()


def test_fused_segment_closure_reads_the_converted_tree(conversions):
    """``traceable_fn`` inlines the narrowed program from one trace of
    the model, on the tree the filter's own programs share; planned
    before a reload it keeps serving the parameters it was planned
    with."""
    fw = _open(VIT)
    traces = []
    apply_fn = fw._apply

    def counting(p, *xs):
        traces.append(1)
        return apply_fn(p, *xs)

    fw._apply = counting
    x = _frames(4)
    fn = fw.traceable_fn()
    fused = jax.jit(fn)
    got = np.asarray(fused(x))
    assert len(traces) == 1 and len(conversions) == 1
    consts = jax.make_jaxpr(fn)(x).consts
    assert sum(c.dtype == jnp.bfloat16 for c in consts) \
        == fw.prepared_report()["prepared_leaves"]
    assert np.asarray(fw.invoke([x])[0]).tobytes() == got.tobytes()
    assert len(conversions) == 1          # the filter's program shares it
    assert fw.handle_event(FilterEvent.RELOAD_MODEL,
                           {"model_files": (VIT + "&seed=1",)})
    again = np.asarray(jax.jit(fn)(x))    # a new trace, after the reload
    assert again.tobytes() == got.tobytes()
    assert len(conversions) == 1
    fw.close()


def test_prepare_span_is_recorded_once_per_load(monkeypatch):
    from nnstreamer_tpu.obs import spans
    said = []
    real = spans.region

    def region(prof, cat, *args, **meta):
        said.append((prof, meta))
        return real(prof, cat, *args, **meta)

    monkeypatch.setattr(spans, "region", region)
    spans.clear()
    fw = _open(VIT)
    fw.invoke([_frames(1)])
    fw.invoke([_frames(4)])
    rows = [s for _, s in spans.snapshot() if s[0] == "nns.filter.prepare"]
    assert len(rows) == 1 and rows[0][1] == "filter"
    rep = fw.prepared_report()
    leaves = jax.tree.leaves(fw._params)
    assert [m for p, m in said if p == "nns.filter.prepare"] == [{
        "leaves": rep["prepared_leaves"],
        "equations": rep["prepared_equations"],
        "bytes_in": sum(leaves[i].nbytes for i in fw._cut.sources),
        "bytes_out": rep["prepared_bytes"]}]
    fw.close()


def test_relaid_leaves_through_the_filter(tmp_path, conversions):
    """The toy of ``test_split_moves_...`` as a model file: the same
    bytes as ``jax.jit`` of its ``apply_fn``, the counters and the
    span's ``equations=`` pinned, no leaf counted as narrowed."""
    import inspect
    body = inspect.getsource(_relaid) + inspect.getsource(_relaid_params)
    model = tmp_path / "model.py"
    model.write_text(
        "import jax\nimport jax.numpy as jnp\nimport numpy as np\n"
        "from nnstreamer_tpu.tensors.info import TensorsInfo\n" + body
        + "def get_model():\n    return (_relaid, _relaid_params(), "
        "TensorsInfo.make('float32', '16:8'), None)\n")
    fw = _open(str(model))
    x = np.random.default_rng(1).standard_normal((8, 16)).astype(np.float32)
    got = fw.invoke([x])
    assert fw.prepared_report() == {"prepared_leaves": 0,
                                    "prepared_bytes": 0,
                                    "prepared_equations": 6,
                                    "kernel_calls": {}}
    assert conversions == [1]
    want = jax.jit(fw._apply)(fw._params, x)
    assert [np.asarray(a).tobytes() for a in got] \
        == [np.asarray(b).tobytes() for b in jax.tree.leaves(want)]
    fw.invoke([x])
    assert conversions == [1]
    fw.close()


# -- tools/aot_estimate.py ------------------------------------------------

@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return "v5e:2x2"


def _tool():
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "aot_estimate.py"
    spec = importlib.util.spec_from_file_location("aot_estimate", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_aot_estimate_smoke(topology):
    """The TPU compiler takes the narrowed program, and it holds no
    conversion of a float32 kernel that the program as loaded has."""
    tool = _tool()
    model = "zoo://vit?size=32&patch=8&d_model=128&layers=2&heads=4&classes=10"
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        narrowed = tool.compile_text(model, 4, topology)
        loaded = tool.compile_text(model, 4, topology, as_loaded=True)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "f32[128,512]" in loaded and "f32[128,512]" not in narrowed
    rows = tool.fusion_cycles(narrowed)
    assert rows and all(n > 0 and c > 0 for n, c in rows.values())
    assert sum(c for _, c in rows.values()) \
        < sum(c for _, c in tool.fusion_cycles(loaded).values())


@pytest.mark.parametrize("lo,hi,masked,dv", [(3584, 4096, True, 256),
                                             (1536, 2048, False, 256),
                                             (3584, 4096, False, 128)],
                         ids=["masked", "causal", "causal_v128"])
def test_mosaic_takes_the_masked_attention_kernel(topology, lo, hi, masked,
                                                  dv):
    """What the interpreter cannot show: the TPU's compiler lays out
    ``nns_masked_attention`` at the lm cells' widths (64 heads, S =
    4096, the module's own tiles; keys of 256 lanes with values of 256,
    GLM-5's, or of 128, LongCat's 192 | 128 padded) within the VMEM it
    asks for."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from nnstreamer_tpu.ops import sparse_attention as sa
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def block(q, k, v, keep, out):
        return sa._attend_block(q, k, v, keep if masked else None, out,
                                lo=lo, hi=hi, tq=sa.TILE_Q, tk=sa.TILE_K,
                                scale=1 / 16, interpret=False)

    heads, values = spec((64, 4096, 256)), spec((64, 4096, dv))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        # conftest pins float32 products for the CPU's sake; the chip
        # multiplies bfloat16 operands as they are
        with jax.default_matmul_precision("default"):
            text = jax.jit(block).lower(
                heads, heads, values, spec((hi - lo, hi), jnp.int8), values
            ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text and "nns_masked_attention" in text


@pytest.mark.parametrize("lo,hi,window,tiles", [(3584, 4096, 2048, 5),
                                                (2048, 2560, 2048, 5),
                                                (3584, 4096, None, 8)],
                         ids=["window_last", "window_first_behind", "full"])
def test_mosaic_takes_the_window_walk_over_shared_heads(topology, lo, hi,
                                                        window, tiles):
    """The TPU's compiler lays out ``nns_masked_attention`` at the
    Trinity-Mini cell's widths: 32 query heads of 128 on 4 key/value
    heads (``[4, S, 128]`` operands, nothing repeated), S = 4096, the
    module's own tiles, a window of 2048: the grid holds the key tiles
    a query tile walks and none behind its window (5 where the full
    layer's last block walks 8)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from nnstreamer_tpu.ops import sparse_attention as sa
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0])

    def spec(heads):
        return jax.ShapeDtypeStruct((heads, 4096, 128), jnp.bfloat16,
                                    sharding=dev)

    def block(q, k, v, out):
        return sa._attend_block(q, k, v, None, out, lo=lo, hi=hi,
                                tq=sa.TILE_Q, tk=sa.TILE_K, scale=128 ** -0.5,
                                interpret=False, window=window)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with jax.default_matmul_precision("default"):
            lowered = jax.jit(block).lower(spec(32), spec(4), spec(4),
                                           spec(32))
            text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text and "nns_masked_attention" in text
    shapes = [jax.ShapeDtypeStruct((h, 4096, 128), jnp.bfloat16)
              for h in (32, 4, 4, 32)]
    call, = [e for e in jax.make_jaxpr(block)(*shapes).eqns
             if e.primitive.name == "pallas_call"]
    assert tuple(call.params["grid_mapping"].grid) == (16, 1, tiles)


def test_mosaic_takes_the_chunked_recurrence_at_the_cells_widths(
        topology, monkeypatch):
    """The TPU's compiler lays out ``ops/kda.py``'s two kernels at the
    Kimi-Linear cell's widths: 32 heads of 128, S = 8192, chunks of 64
    (an interpret-mode run shows neither an unaligned slice nor what a
    kernel may hold in VMEM), and the grouped kernel at half a router:
    128 experts of 2304 x 1024 over 8192 tokens choosing 8 of 256. The
    op asks ``jax.default_backend()`` whether to interpret; this
    process' answer is steered here, not by an option of the program."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from nnstreamer_tpu.ops import grouped, kda
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def experts(x, choice, weight, w1, w3, w2):
        order, counts = grouped.group_by_expert(choice, 0, 128)
        return grouped.grouped_swiglu(x, order, counts, weight, w1, w3, w2,
                                      tile=256, router=256)

    head = (32, 8192, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with jax.default_matmul_precision("default"):
            text = jax.jit(lambda *a: kda.kda_chunked(*a, chunk=64)).lower(
                spec(head), spec(head), spec(head), spec(head, jnp.float32),
                spec(head[:2], jnp.float32)).compile().as_text()
            half = jax.jit(experts).lower(
                spec((8192, 2304)), spec((8192, 8), jnp.int32),
                spec((8192, 8), jnp.float32), spec((128, 2304, 1024)),
                spec((128, 2304, 1024)), spec((128, 1024, 2304))
            ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "nns_kda_chunk_intra" in text and "nns_kda_chunk_state" in text
    assert half.count('custom_call_target="tpu_custom_call"') == 1
    assert "nns_grouped_swiglu" in half


def test_mosaic_takes_the_power_retention_at_the_cells_widths(
        topology, monkeypatch):
    """The TPU's compiler lays out ``ops/power_retention.py``'s kernel
    at the Brumby cell's widths: 40 query heads on 8 key/value heads of
    128 over a buffer of 4096 tokens in chunks of 512, a head's state of
    8320 x 128 float32 resident in VMEM through its chunks (an
    interpret-mode run shows neither an unaligned slice, nor a broadcast
    the compiler has not, nor what a kernel may hold in VMEM), the state
    aliased into its output."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from nnstreamer_tpu.ops import power_retention as op
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(
                lambda q, k, v, g, s, z: op.power_retention(
                    q, k, v, g, (s, z), chunk=512),
                donate_argnums=(4, 5)).lower(
                spec((40, 4096, 128)), spec((8, 4096, 128)),
                spec((8, 4096, 128)), spec((8, 4096), jnp.float32),
                spec((8, 8320, 128), jnp.float32),
                spec((8, 128, 128), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        op._call.clear_cache()      # traced for the chip: not this process'
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "nns_power_retention" in text
    # the donated state is the kernel's output: no second copy of it
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 8 * (8320 + 128) * 128 * 4


@pytest.mark.parametrize("model,calls", [
    ("zoo://longcat?seq=128&v_head_dim=128&held_first=4&held_count=4", 4),
    ("zoo://glm_dsa?seq=128&v_head_dim=128&held_first=8&held_count=8", 3),
    ("zoo://afmoe?seq=128&head_dim=128&held_first=4&held_count=4", 4),
], ids=["longcat", "glm_dsa", "afmoe"])
def test_aot_estimate_compiles_an_lm_models_kernel(topology, model, calls):
    """For a TPU topology the tool hands the attention kernel to Mosaic
    (this process' backend is the CPU, which the model would answer with
    the interpreter's loops), and its list of stand-alone moves shows no
    head-major activation padded or copied on its way to the kernel: q
    and k leave their products 128 lanes wide."""
    tool = _tool()
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with jax.default_matmul_precision("default"):
            text = tool.compile_text(model, 0, topology)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert "nns_masked_attention" in text
    moves = tool.plain_moves(text)
    assert all(op in ("copy", "pad") and n > 0
               for (op, _, _), n in moves.items())
    assert not [m for m in moves if "[4,128,128]" in m[2]]


def test_aot_estimate_lists_the_moves_that_stand_alone():
    """``plain_moves`` counts a ``copy`` / ``pad`` of the entry or of a
    loop's body with its operand's shape, and none inside a fusion."""
    text = textwrap.dedent("""\
        HloModule jit_nns_filter_toy

        %fused_computation (param_0.1: bf16[8,16]) -> bf16[8,128] {
          %param_0.1 = bf16[8,16]{1,0} parameter(0)
          %constant.1 = bf16[] constant(0)
          ROOT %pad.9 = bf16[8,128]{1,0} pad(%param_0.1, %constant.1), padding=0_0x0_112
        }

        %body (p: (bf16[8,16])) -> (bf16[8,16]) {
          %p = (bf16[8,16]{1,0}) parameter(0)
          %gte = bf16[8,16]{1,0} get-tuple-element(%p), index=0
          %copy.3 = bf16[8,16]{0,1} copy(%gte)
          ROOT %tuple = (bf16[8,16]{0,1}) tuple(%copy.3)
        }

        ENTRY %main (Arg_0.1: bf16[8,16], Arg_1.2: bf16[4,8,16]) -> bf16[8,128] {
          %Arg_0.1 = bf16[8,16]{1,0} parameter(0)
          %Arg_1.2 = bf16[4,8,16]{2,1,0} parameter(1)
          %constant.2 = bf16[] constant(0)
          %pad.1 = bf16[4,8,128]{2,1,0} pad(%Arg_1.2, %constant.2), padding=0_0x0_0x0_112
          %pad.2 = bf16[4,8,128]{2,1,0} pad(%Arg_1.2, %constant.2), padding=0_0x0_0x0_112
          %copy.1 = bf16[8,16]{0,1} copy(%Arg_0.1)
          ROOT %fusion = bf16[8,128]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation
        }
        """)
    assert _tool().plain_moves(text) == {
        ("copy", "bf16[8,16]{1,0}", "bf16[8,16]{0,1}"): 2,
        ("pad", "bf16[4,8,16]{2,1,0}", "bf16[4,8,128]{2,1,0}"): 2}
