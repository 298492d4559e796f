"""The JAX/XLA TPU filter backend — this framework's native inference engine.

Where the reference fans out to 30 vendor SDK subplugins
(ref: ext/nnstreamer/tensor_filter/*, SURVEY.md §2.5), the TPU-native
design collapses them into one backend: a model resolves to a pure
``apply_fn(params, *inputs)``, params live in HBM, and invoke dispatches a
**cached jax.jit executable per input signature** (≙ the reference's
fw->invoke hot call, tensor_filter.c:1227, with the EdgeTPU/TensorRT
engine-cache idea done the XLA way).

Model URIs accepted by the ``model`` property:
  * ``zoo://<name>?k=v&...``  — in-repo model zoo (flax), deterministic
    random init unless ``params_dir=<orbax dir>`` is given.
  * ``<file>.jaxm.py``        — a python module defining
    ``get_model() -> (apply_fn, params, input_info, output_info)``.
  * ``<dir>`` with orbax checkpoint + ``model.json`` zoo spec.

**A state the filter keeps.** ``get_model()`` (and a zoo builder) may
return a fifth item, the tree of the model's state before its first
buffer. The program is then ``apply_fn(params, state, *inputs) ->
(outputs, state)``: the filter places the tree on its device, hands it
to every invoke, donated, and keeps what comes back for the next, in
stream order (the lock that orders dispatches orders the states), with
no host synchronisation: the next dispatch takes the still-materialising
arrays, so an in-flight window stays as deep as it is. The state never
leaves the device between buffers; suspend moves it to the host with the
parameters and resume back. A reload, ``close()`` and a failed invoke
drop it: the next buffer meets the initial state again
(``transfer_report()["state"]["drops"]`` counts those). When a document
starts is the model's to read off its inputs. The reference closes
recurrent loops on the host (``tensor_repo``); that stays for arbitrary
graphs, and this is the form in which a model's own state never leaves
HBM. A four-item model runs the code it ran before there was a fifth.

Outputs stay device-resident (jax.Array) so chained elements keep HBM
residency; they materialize only at host boundaries.

**Mesh mode** (multi-chip invoke): ``custom=mesh:<dp>x<sp>x<tp>`` (or
``mesh:auto``) builds a `jax.sharding.Mesh`, places params by the
``rules:`` table (``gpt`` = Megatron TP from parallel/sharding.py;
default = replicate), and shards the input batch over the ``data`` axis,
so one invoke fans out over every chip with XLA inserting the ICI
collectives. This is the TPU-native answer to the reference's
among-device stream fan-out (ref: tensor_query/README.md:5-27 — there,
frames are RPC'd to other devices; here the mesh IS the device pool).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import urllib.parse
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import load as _obs_load
from ..obs import spans as _obs_spans
from ..tensors.info import TensorsInfo
from ..utils.log import logger
from ..utils.xla_cache import ensure_compile_cache
from . import prepare as _prepare
from .base import (Accelerator, FilterEvent, FilterFramework,
                   FilterProperties,
                   parse_custom_properties as _parse_custom)
from .registry import register_filter


def _device_for(accelerators: Sequence[Accelerator]):
    """First usable entry of the ``accelerator`` preference list (the
    reference's ``true:tpu.cpu`` grammar): a NAMED platform must exist —
    JAX itself falls back to CPU with a warning when the TPU runtime
    does not come up, and a filter that asked for the TPU must not then
    serve from the host as if nothing happened. ``default`` (and an
    empty property) is whatever JAX's default backend is. Every
    non-mesh filter lands on device 0 of its platform, also on a
    four-chip host; spreading filters over chips is ``custom=mesh:``."""
    import jax
    missing = []
    for acc in accelerators:
        if acc == Accelerator.DEFAULT:
            return jax.devices()[0]
        # accelerator=false / cpu is an explicit opt-out of the TPU
        platform = "cpu" if acc == Accelerator.NONE else acc.value
        try:
            return jax.devices(platform)[0]
        except RuntimeError:
            missing.append(platform)
    if missing:
        raise RuntimeError(
            f"accelerator {'.'.join(missing)} requested but JAX has no "
            f"such backend here (default backend: "
            f"{jax.default_backend()})")
    return jax.devices()[0]


class _Held:
    """What ``dispatch`` hands out for a model that carries a state:
    the outputs, and which state (by its drop count) the frame ran on."""

    __slots__ = ("out", "gen")

    def __init__(self, out, gen):
        self.out, self.gen = out, gen


@register_filter
class JaxFilter(FilterFramework):
    """framework=jax (aliases: jax-tpu). The flagship backend."""

    NAME = "jax"
    EXTENSIONS = (".py", ".jaxm", ".msgpack")
    SUPPORTS_BATCH = True  # apply fns broadcast over a leading batch dim
    # JAX dispatch is async on every backend: dispatch() below returns
    # as soon as the executable is enqueued, complete() blocks — the
    # split the element's in-flight window is built on
    SUPPORTS_DISPATCH = True

    # platforms where jax.jit honors donate_argnums (CPU logs a warning
    # per donated arg and ignores it — gate rather than spam)
    _DONATION_PLATFORMS = ("tpu", "gpu")

    def __init__(self):
        self._apply: Optional[Callable] = None
        # the tree as the model handed it over: what reload and suspend read
        self._params: Any = None
        # filters/prepare.py: how the first program traced after a
        # load was cut (None: none traced yet), and what its step reads
        # of the parameters: the leaves it kept and the load's results,
        # which every program whose cut agrees runs on
        self._cut: Optional[_prepare.Split] = None
        self._prepared: Optional[List[Any]] = None
        # {kernel name: call sites} in that first program's trace
        self._kernel_calls: Dict[str, int] = {}
        # jit-cache keys of the programs that take the prepared arrays
        self._on_prepared: set = set()
        self._in_info: Optional[TensorsInfo] = None
        self._out_info: Optional[TensorsInfo] = None
        # a model that carries a state between buffers (a fifth item of
        # get_model()): the tree before the first buffer as the model
        # gave it, the leaves as the last dispatch returned them (None:
        # a stateless model, and a suspended or closed one), their tree,
        # and the account transfer_report()["state"] gives
        self._state0: Any = None
        self._state: Optional[List[Any]] = None
        self._state_tree: Any = None
        self._state_bytes = 0
        self._state_gen = 0       # rises with every drop
        self._state_stats = {"dispatches": 0, "drops": 0}
        self._jit_cache: Dict[Tuple, Any] = {}
        self._device = None
        self._mesh = None
        self._param_sharding = None
        self._props: Optional[FilterProperties] = None
        self._lock = threading.Lock()
        self._suspended = False
        # monotonically counts jit-cache misses (actual trace+compile),
        # warmup and prewarm included — the element baselines it at
        # start() so its jit_recompiles stat counts only frame-path
        # compiles (the jit-stability gate pins those to zero)
        self.compile_count = 0
        # persistent compile cache identity (fleet/cache.py): model URI
        # + mesh spec — donation variants key per entry, not per model
        self._cache_key = ""
        # names the jitted program ``jit_nns_filter_<stem>`` in a trace
        self._model_stem = "model"
        # obs/load.py: the load's spans in seconds and one record per
        # program built, what ``load_report()`` gives
        self._load_log = _obs_load.LoadLog()

    # -- lifecycle --------------------------------------------------------
    def open(self, props: FilterProperties) -> None:
        import jax
        ensure_compile_cache()  # before _load_model: zoo init compiles
        if _obs_spans.ENABLED:
            _obs_load.install()
        self._props = props
        opts = _parse_custom(props.custom_properties)
        model = props.model_files[0] if props.model_files else ""
        with self._load_log.phase("model", model=model) as span:
            self._load_model(model, props)
            leaves = jax.tree.leaves(self._params)
            held = sum(getattr(x, "nbytes", 0) for x in leaves)
            span.note(leaves=len(leaves), bytes=held)
        if "mesh" in opts:
            from ..parallel.mesh import mesh_from_spec
            from ..parallel.sharding import named_sharding_tree, rules_by_name
            self._mesh = mesh_from_spec(opts["mesh"])
            rules = rules_by_name(opts.get("rules", ""))
            self._param_sharding = named_sharding_tree(
                self._params, rules, self._mesh)
            self._place(self._param_sharding, held, self._mesh.devices.size)
            logger.info("jax filter opened model=%s on mesh %s", model,
                        dict(self._mesh.shape))
        else:
            self._device = _device_for(props.accelerators)
            self._place(self._device, held, 1)
            logger.info("jax filter opened model=%s on %s", model,
                        self._device)
        if self._state0 is not None:
            if self._mesh is not None:
                raise ValueError(
                    f"{model}: a model that carries a state between "
                    "buffers runs on one chip; custom=mesh: cannot place "
                    "its state")
            self._place_state()
        self._cache_key = f"{model}|mesh={opts.get('mesh', '')}"
        self._prewarm_from_cache()
        if self._state0 is not None and self._state_stats["dispatches"]:
            # the replayed signatures ran on zeros: not the stream's
            self._place_state()
            self._state_stats["dispatches"] = 0

    def _place(self, where: Any, held: int, devices: int) -> None:
        """The loaded tree onto its device or mesh. ``device_put`` may
        return before the copies are done: the span times this thread,
        as ``nns.transfer.upload`` does."""
        if self._params is None:
            return
        import jax
        with self._load_log.phase("place", bytes=held, devices=devices):
            self._params = jax.device_put(self._params, where)

    def _place_state(self) -> None:
        """The initial state onto the device (lock held, or before the
        first invoke), each leaf an array of its own that a dispatch may
        donate: a leaf that already lies there is copied."""
        import jax
        import jax.numpy as jnp
        leaves, self._state_tree = jax.tree.flatten(self._state0)
        placed = [jax.device_put(x, self._device) for x in leaves]
        self._state = [jnp.copy(y) if y is x else y
                       for x, y in zip(leaves, placed)]
        self._state_bytes = sum(int(x.nbytes) for x in self._state)

    def _drop_state(self, gen: Optional[int] = None) -> None:
        """Forget the carried state (lock held): the next buffer meets
        the initial one. ``gen``: only if no drop came since the
        dispatch that failed took its state."""
        if self._state0 is None or gen not in (None, self._state_gen):
            return
        self._state_gen += 1
        self._state_stats["drops"] += 1
        if not self._suspended:
            self._place_state()

    def state_report(self) -> Optional[Dict[str, int]]:
        """``{leaves, bytes, dispatches, drops}`` of the carried state
        (``drops``: states lost to an error or a reload); None for a
        model that carries none."""
        if self._state0 is None:
            return None
        return {"leaves": self._state_tree.num_leaves,
                "bytes": self._state_bytes, **self._state_stats}

    def _prewarm_from_cache(self) -> None:
        """Replay every signature this model compiled in previous lives
        (fleet/cache.py): the jit cache is hot BEFORE the first frame
        arrives — and before a serve pipeline REGISTERs on the broker —
        so a resurrected or scaled-up replica's first-frame latency is
        steady-state, not compile-bound."""
        from ..fleet import cache as compile_cache
        cc = compile_cache.active()
        if cc is None or self._apply is None:
            return
        import jax
        warmed = 0
        for sig, donate in cc.signatures("jax", self._cache_key):
            if donate and (self._device is None or self._device.platform
                           not in self._DONATION_PLATFORMS):
                donate = ()  # recorded on a donating platform; not here
            try:
                xs = [np.zeros(shape, dtype) for shape, dtype in sig]
                if self._mesh is not None:
                    xs = self._place_inputs(xs)
                else:
                    xs = [jax.device_put(x, self._device) for x in xs]
                jax.block_until_ready(self._run(xs, donate))
                warmed += 1
            except (TypeError, ValueError) as exc:
                # a stale signature (model shape change across versions)
                # fails at TRACE time and only costs its own replay; a
                # device-side failure (XlaRuntimeError) is not stale
                # data and fails the open
                logger.warning("jax filter: cached signature %s no "
                               "longer traces, skipped: %s", sig, exc)
        if warmed:
            logger.info("jax filter: prewarmed %d signature(s) for %s",
                        warmed, self._cache_key)

    def _load_model(self, model: str, props: FilterProperties) -> None:
        self._model_stem = os.path.splitext(os.path.basename(
            model.rstrip("/")))[0]
        if model.startswith("zoo://"):
            from ..models import zoo
            parsed = urllib.parse.urlparse(model)
            kwargs = {k: v[0] for k, v in
                      urllib.parse.parse_qs(parsed.query).items()}
            name = parsed.netloc or parsed.path.lstrip("/")
            self._model_stem = name
            self._take_model(zoo.build(name, **kwargs))
        elif model.endswith(".py"):
            ns: Dict[str, Any] = {}
            with open(model) as f:
                code = f.read()
            exec(compile(code, model, "exec"), ns)  # noqa: S102 - user script, like python3 subplugin
            if "get_model" not in ns:
                raise ValueError(f"{model}: must define get_model()")
            self._take_model(ns["get_model"]())
        elif os.path.isdir(model) and os.path.exists(
                os.path.join(model, "model.json")):
            with open(os.path.join(model, "model.json")) as f:
                spec = json.load(f)
            from ..models import zoo
            self._take_model(zoo.build(
                spec["name"], params_dir=model, **spec.get("kwargs", {})))
        else:
            raise ValueError(f"jax backend cannot load model {model!r}")

    def _take_model(self, model: Tuple) -> None:
        """What ``get_model()`` or a zoo builder returned: four items,
        or five with the state before the first buffer."""
        (self._apply, self._params, self._in_info, self._out_info,
         *state) = model
        self._state0 = state[0] if state else None

    def close(self) -> None:
        self._apply = None
        self._params = None
        self._state0 = self._state = None
        self._drop_programs()

    def _drop_programs(self) -> None:
        """Forget what was built from the parameters that are being
        replaced or unloaded: the programs, the load's results and the
        cut they were made by. The next program built redoes all three
        from ``self._params`` as it then stands."""
        self._jit_cache.clear()
        self._on_prepared.clear()
        self._cut = self._prepared = None
        self._kernel_calls = {}

    def prepared_report(self) -> Dict[str, Any]:
        """How many parameter leaves the loaded model holds a second
        time in their compute dtype, and that copy's bytes (0, 0 where
        no leaf qualified); ``prepared_equations``: the equations of
        the model's trace that run once per load and not per buffer,
        those conversions among them (0 where the leaves alone
        determine nothing); and ``kernel_calls``: the Pallas kernels of
        the first program traced after the load, by name, with their
        call sites ({} for a model in plain XLA). The element's
        ``transfer_report()``."""
        narrowed = self._cut.narrowed if self._cut else {}
        return {"prepared_leaves": len(narrowed),
                "prepared_bytes": sum(narrowed.values()),
                "prepared_equations":
                    self._cut.equations if self._cut else 0,
                "kernel_calls": dict(self._kernel_calls)}

    def load_report(self) -> Optional[Dict[str, Any]]:
        """What the load cost, from its own spans: ``model_s`` (the model
        file's ``get_model()`` / ``zoo.build``), ``place_s`` (the tree's
        ``device_put``) and ``programs``, one record per program built
        (``obs/load.py``: name, signature, ``at`` ``load`` or ``frame``,
        wall, trace, lower and compile seconds, the cache's verdict);
        None with recording off. The element adds its ``start_s``,
        ``first_buffer_s`` and ``total_s`` and hands the block out as
        ``transfer_report()["load"]``."""
        return self._load_log.report()

    def first_buffer_done(self, element: str) -> None:
        """``element``'s first buffer is through: a program built from
        now on is a recompile on its frame path."""
        self._load_log.serving = element

    # -- info -------------------------------------------------------------
    def get_model_info(self):
        return self._in_info, self._out_info

    # -- invoke -----------------------------------------------------------
    def _run(self, xs: Sequence[Any],
             donate_idx: Tuple[int, ...] = ()) -> Any:
        """Enqueue the program for placed inputs ``xs`` (lock held).
        One compiled executable per input signature (shape/dtype tuple).
        Recompile-on-new-signature is the static-shape answer to dynamic
        models (SURVEY.md §7 hard part (a)). ``donate_idx`` (1-based:
        arg 0 is params, which are NEVER donated) selects inputs whose
        device buffers XLA may alias into the outputs; it is part of the
        cache key because donation changes the compiled program.

        The model is traced here, once per program, and the program is
        built from that trace: without what the load computed, where
        the trace is cut as the loaded model's first one was
        (filters/prepare.py), else ``jax.jit`` of ``apply_fn`` itself
        on the loaded tree."""
        if self._state0 is not None:
            return self._run_held(xs, donate_idx)
        sig = tuple((tuple(x.shape), str(x.dtype)) for x in xs)
        key = (sig, donate_idx) if donate_idx else sig
        exe = self._jit_cache.get(key)
        if exe is None:
            # the span ends with the first call's return: lowering and
            # the backend compile (or the cache's retrieval) happen there
            with self._load_log.program(
                    "jit_" + _obs_spans.identifier(
                        "nns_filter_" + self._model_stem), sig, donate_idx):
                exe = self._build(key, sig, xs, donate_idx)
                return exe(self._prepared if key in self._on_prepared
                           else self._params, *xs)
        return exe(self._prepared if key in self._on_prepared
                   else self._params, *xs)

    def flat_apply(self) -> Callable:
        """A stateful model's program over flat arguments, ``fn(params,
        *state leaves, *inputs) -> (outputs, next state's leaves)``:
        what :meth:`_run_held` jits (and ``tools/aot_estimate.py``
        compiles for a described chip)."""
        import jax
        apply_fn, tree = self._apply, jax.tree.structure(self._state0)
        n = tree.num_leaves

        def fn(params, *flat):
            out, new = apply_fn(params, jax.tree.unflatten(tree, flat[:n]),
                                *flat[n:])
            return out, jax.tree.leaves(new)

        return fn

    def _run_held(self, xs: Sequence[Any],
                  donate_idx: Tuple[int, ...]) -> Any:
        """:meth:`_run` for a model that carries a state (lock held):
        the program takes the parameters, the state's leaves and the
        inputs, the leaves donated, and returns the outputs and the next
        leaves, which are kept as they are, still materialising: the
        next dispatch queues behind this one on the device and no host
        thread waits. Whatever fails here drops the state."""
        state = self._state
        n = len(state)
        sig = tuple((tuple(x.shape), str(x.dtype)) for x in xs)
        key = (sig, donate_idx, "state")
        exe = self._jit_cache.get(key)
        if _obs_spans.ENABLED:
            _obs_spans.record_span(
                "nns.filter.state", "filter", time.time_ns(), 0,
                prof="nns.filter.state", leaves=n, bytes=self._state_bytes)
        # as in _run, the span ends with the first call's return
        span = contextlib.nullcontext() if exe is not None else \
            self._load_log.program(
                "jit_" + _obs_spans.identifier(
                    "nns_filter_" + self._model_stem), sig, donate_idx)
        try:
            with span:
                if exe is None:
                    donate = tuple(i + n for i in donate_idx)
                    if self._device.platform in self._DONATION_PLATFORMS:
                        donate = tuple(range(1, n + 1)) + donate
                    exe = self._build(key, sig, [*state, *xs], donate_idx,
                                      self.flat_apply(), donate)
                out, new = exe(self._prepared if key in self._on_prepared
                               else self._params, *state, *xs)
        except BaseException:
            self._drop_state()
            raise
        self._state = list(new)
        self._state_stats["dispatches"] += 1
        return out

    def _build(self, key: Tuple, sig: Tuple, xs: Sequence[Any],
               donate_idx: Tuple[int, ...], apply: Optional[Callable] = None,
               donate: Optional[Tuple[int, ...]] = None) -> Any:
        """The program for a signature the jit cache missed (lock
        held), traced, cut and cached; compiled by its first call.
        ``apply`` and ``donate`` are :meth:`_run_held`'s: the program
        over the state's leaves and the inputs (``xs`` then holds both:
        to the cut the state is an input, never a leaf) and what of it
        is donated; without them the model's ``apply_fn`` and
        ``donate_idx``."""
        import jax
        if donate is None:
            donate = donate_idx

        def jit(fn):
            # a stable program name for the trace's XLA Modules line
            fn = _obs_spans.named_program(
                "nns_filter_" + self._model_stem, fn)
            return jax.jit(fn, donate_argnums=donate) \
                if donate else jax.jit(fn)

        exe = jit(apply or self._apply)
        with self._load_log.trace() as span:
            closed, out_tree, cut = _prepare.trace(exe, self._params, xs)
            span.note(equations=len(closed.jaxpr.eqns))
        if self._cut is None:
            self._kernel_calls = _prepare.kernel_calls(closed)
        if self._loaded(self._params, cut) is not None:
            exe = jit(_prepare.program(closed, out_tree, cut))
            self._on_prepared.add(key)
        self._jit_cache[key] = exe
        self.compile_count += 1
        self._record_signature(sig, donate_idx)
        return exe

    def _loaded(self, params: Any, cut: _prepare.Split) -> Any:
        """What a program's step may read whose trace of ``params`` was
        cut into ``cut`` (lock held): the loaded model's kept leaves
        and load results, shared by every signature, or None where the
        trace does not agree with them: nothing goes to the load, the
        cut is another than the first program's (what the leaves alone
        determine is ``apply_fn``'s, not the input shape's, so this is
        a guard), or ``params`` are no longer the loaded ones (a fused
        segment planned before a reload). The first trace after a load
        decides the cut and runs its load."""
        if params is not self._params:
            return None
        if self._cut is None:
            self._cut = cut
            if cut:
                self._prepared = self._load(cut)
        return self._prepared \
            if cut and cut.agrees == self._cut.agrees else None

    def _load(self, cut: _prepare.Split) -> List[Any]:
        import jax
        leaves = jax.tree.leaves(self._params)
        # a fused segment reaches here inside ITS trace: the load must
        # run now, not be staged into that program
        bytes_out = sum(v.aval.size * v.aval.dtype.itemsize
                        for v in cut.load.outvars)
        with self._load_log.prepare(
                leaves=len(cut.sources), equations=cut.equations,
                bytes_in=sum(leaves[i].nbytes for i in cut.sources),
                bytes_out=bytes_out), jax.ensure_compile_time_eval():
            out = _prepare.load(cut, leaves)
        logger.info("jax filter: %d equations on %d parameter leaves run "
                    "once for %s, %d bytes held", cut.equations,
                    len(cut.sources), self._model_stem, bytes_out)
        return [leaves[i] for i in cut.kept] + out

    def _record_signature(self, sig: Tuple,
                          donate_idx: Tuple[int, ...]) -> None:
        """Persist a freshly-compiled signature so the NEXT incarnation
        of this model prewarms it (no-op without an installed cache)."""
        from ..fleet import cache as compile_cache
        cc = compile_cache.active()
        if cc is None or not self._cache_key:
            return
        try:
            cc.record("jax", self._cache_key, sig, donate_idx)
        except Exception as exc:  # cache IO is never allowed to fail serving
            logger.warning("jax filter: compile-cache record failed: %s",
                           exc)

    @property
    def mesh(self):
        """The live Mesh in mesh mode (None per-chip) — read by the
        fused-segment compiler, the in-flight window's per-mesh slot
        accounting, and the pipeline report's devices fields."""
        return self._mesh

    def _input_sharding(self, x):
        """Shard the batch (dim 0) over the ``data`` axis when divisible;
        replicate otherwise. XLA propagates from these committed inputs +
        the param shardings and inserts the ICI collectives."""
        from ..parallel.sharding import batch_sharding
        return batch_sharding(self._mesh, x.ndim,
                              x.shape[0] if x.ndim else 0)

    def _place_inputs(self, inputs):
        """Mesh placement of one invoke's inputs. An input the serve
        scheduler already committed with the wanted sharding passes
        through untouched — placement upstream (overlapped with
        batching) makes the dispatch leg here O(1), which keeps the
        windowed dispatch/complete latency split honest for sharded
        programs."""
        import jax
        xs = []
        for x in inputs:
            if isinstance(x, jax.Array):
                if x.sharding == self._input_sharding(x):
                    xs.append(x)
                    continue
                # device-resident but laid out differently: reshard on
                # device (device_put only reads shape/ndim on the host)
                xs.append(jax.device_put(x, self._input_sharding(x)))
            else:
                x = np.asarray(x)
                xs.append(jax.device_put(x, self._input_sharding(x)))
        return xs

    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        import jax
        with self._lock:
            if self._suspended:
                self._resume()
            if self._mesh is not None:
                xs = self._place_inputs(inputs)
            else:
                # a mesh-committed upstream output (sharded filter or
                # serve placement) must collapse to this chip: jit
                # refuses mixed device sets otherwise
                xs = [x if isinstance(x, jax.Array)
                      and len(x.sharding.device_set) == 1 else
                      jax.device_put(x if isinstance(x, jax.Array)
                                     else np.asarray(x), self._device)
                      for x in inputs]
            out = self._run(xs)
            gen = self._state_gen if self._state0 is not None else None
        if gen is not None:
            # a failure must not reach the next buffer's state
            out = self._ready(out, gen)
        if isinstance(out, (list, tuple)):
            return list(out)
        return [out]

    def _ready(self, out: Any, gen: Optional[int]) -> Any:
        """``out`` once it is materialised (no lock held: only the
        arrays are touched). Where that raises and the frame ran on a
        model's carried state (``gen``: that state's drop count), the
        state its successors were built on is dropped, under the lock
        and once for the state: the frames dispatched behind it fail on
        their own."""
        import jax
        try:
            return jax.block_until_ready(out)
        except BaseException:
            if gen is not None:
                with self._lock:
                    self._drop_state(gen)
            raise

    # -- overlapped execution ---------------------------------------------
    def dispatch(self, inputs: Sequence[Any], donate: bool = False) -> Any:
        """Enqueue one frame's executable and return the (still
        materializing) output arrays as the in-flight handle — JAX
        dispatch is async, so this returns as soon as XLA has the
        program queued; errors surface in :meth:`complete`.

        Donation: with ``donate=True`` the H2D staging buffers of inputs
        THIS call uploaded are donated to the executable
        (input/output aliasing — the double-buffered H2D leg reuses its
        staging buffer for the outputs instead of allocating fresh HBM
        per in-flight frame). Device-resident inputs are upstream-owned
        and never donated; params (arg 0) never either. Gated to
        platforms where XLA honors donation — CPU ignores it with a
        warning per arg."""
        import jax
        with self._lock:
            if self._suspended:
                self._resume()
            donate_idx: Tuple[int, ...] = ()
            if self._mesh is not None:
                xs = self._place_inputs(inputs)
            else:
                xs = list(inputs)
                # 1-based: arg 0 is params
                staged = [i + 1 for i, x in enumerate(xs)
                          if not isinstance(x, jax.Array)]
                if staged:
                    host = [np.asarray(xs[i - 1]) for i in staged]
                    with _obs_spans.region(
                            "nns.transfer.upload", "transfer",
                            arrays=len(host),
                            bytes=sum(a.nbytes for a in host)):
                        for i, a in zip(staged, host):
                            xs[i - 1] = jax.device_put(a, self._device)
                for i, x in enumerate(xs):
                    if len(x.sharding.device_set) > 1:
                        # mesh-committed upstream output: collapse to
                        # this chip (upstream-owned, not donated)
                        xs[i] = jax.device_put(x, self._device)
                if donate and staged \
                        and self._device.platform in self._DONATION_PLATFORMS:
                    donate_idx = tuple(staged)
            out = self._run(xs, donate_idx)
            if self._state0 is not None:
                out = _Held(out, self._state_gen)
        return out

    def complete(self, handle: Any) -> List[Any]:
        """Block until a dispatched frame's outputs are on-device
        materialized (raises the deferred device error, if any). Takes
        no lock: runs on the completer thread concurrently with
        dispatch — block_until_ready only touches the arrays. A frame
        of a model that carries a state, failing here, drops that state
        (:meth:`_ready`)."""
        gen = None
        if isinstance(handle, _Held):
            handle, gen = handle.out, handle.gen
        out = self._ready(handle, gen)
        if isinstance(out, (list, tuple)):
            return list(out)
        return [out]

    # -- fusion -----------------------------------------------------------
    def traceable_fn(self) -> Optional[Callable]:
        """Pure ``fn(*inputs) -> outputs`` closure over the current
        apply/params, for the fusion compiler to inline into a larger
        jit program (fusion/segment.py). Params are captured by value:
        the closure stays valid across suspend/reload, it just keeps
        serving the params it was planned with. Traced while they are
        still the loaded ones, it inlines the step that reads the load's
        results, as ``_run`` builds it, from its one trace of the model.

        In mesh mode the closed-over params are mesh-committed
        jax.Arrays, so the fused program compiles over the mesh with
        XLA propagating the param shardings ("computation follows
        data"); the segment pins batch-major layout at each member
        boundary via its sharding constraints, so a fused run stays
        mesh-resident end to end."""
        with self._lock:
            if self._suspended:
                self._resume()
            apply_fn, params = self._apply, self._params
            if apply_fn is None or self._state0 is not None:
                return None     # a carried state has no place in a segment

        def fn(*xs):
            import jax
            closed, out_tree, cut = _prepare.trace(
                jax.jit(apply_fn), params,
                [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs])
            with self._lock:
                held = self._loaded(params, cut)
            if held is None:    # the trace as it is: every leaf an input
                cut, held = _prepare.split(closed, 0), jax.tree.leaves(params)
            return _prepare.program(closed, out_tree, cut)(held, *xs)

        return fn

    # -- events -----------------------------------------------------------
    def handle_event(self, event: FilterEvent, data=None) -> bool:
        if event == FilterEvent.CHECK_HW_AVAILABILITY:
            from ..utils.hw import is_available
            return is_available((data or {}).get("hw", "default"))
        if event == FilterEvent.RELOAD_MODEL:
            # Keep serving with old params while the new ones load
            # (≙ is-updatable reload, nnstreamer_plugin_api_filter.h:359-365)
            assert self._props is not None
            fresh = JaxFilter()
            fresh.open(self._props if data is None else
                       self._props.__class__(**{**self._props.__dict__, **data}))
            with self._lock:
                self._apply, self._params = fresh._apply, fresh._params
                self._in_info, self._out_info = fresh._in_info, fresh._out_info
                if self._state0 is not None:    # the old model's is lost
                    self._state_gen += 1
                    self._state_stats["drops"] += 1
                self._state0, self._state = fresh._state0, fresh._state
                self._state_tree = fresh._state_tree
                self._state_bytes = fresh._state_bytes
                self._mesh = fresh._mesh
                self._param_sharding = fresh._param_sharding
                self._device = fresh._device
                self._drop_programs()
            return True
        if event == FilterEvent.SUSPEND:
            # Drop HBM copies; reopen transparently on next invoke
            # (≙ suspend watchdog unload, tensor_filter.c:1078-1090)
            import jax
            with self._lock:
                self._params = jax.device_get(self._params)
                if self._state is not None:     # kept, beside the parameters
                    self._state = jax.device_get(self._state)
                self._drop_programs()
                self._suspended = True
            return True
        if event == FilterEvent.RESUME:
            with self._lock:
                self._resume()
            return True
        return False

    def _resume(self) -> None:
        import jax
        if self._suspended:
            self._params = jax.device_put(
                self._params, self._param_sharding if self._mesh is not None
                else self._device)
            if self._state is not None:
                self._state = [jax.device_put(x, self._device)
                               for x in self._state]
            self._suspended = False


from .registry import register_alias as _register_alias  # noqa: E402

_register_alias("jax-tpu", "jax")
_register_alias("flax", "jax")
