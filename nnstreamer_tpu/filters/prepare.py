"""What the parameter leaves alone determine, computed once per load.

A model's ``apply_fn(params, *inputs)`` states some work on its
parameters alone: a flax module keeps float32 leaves and converts each
to its compute dtype where it uses it (for ViT-H/14: 2.5 GB of float32
on the vector unit inside the matmul fusions, once per buffer); a
latent-attention decoder cuts, pads and re-lays its projections'
weights for the product that reads them (``models/latent.py``). The
values are constants between model loads, and an equation on constants
gives the same bits whenever it is run.

So the jax filter evaluates its one trace of the model partially, on
the inputs that are constant (:func:`split`): an equation of the flat
trace all of whose operands are parameter leaves, literals or results
of such equations belongs to the load; ``jit_nns_filter_prepare``
computes those once on the device (:func:`load`), and the per-buffer
program is the trace without them (:func:`program`), reading their
results beside the leaves it still reads itself. A leaf whose every use
is a ``convert_element_type`` to a narrower floating dtype, converted
once and never read again in float32, is the one-equation case of it.
Nothing here knows a model: an equation that reads an input, a leaf
that is returned or read together with an input, and whatever is inside
a sub-program (a loop, a branch, ``jit``, ``pallas_call``) stay where
they are, and a program in which nothing qualifies is not touched at
all.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Split:
    """One trace cut in two. ``load`` maps the ``sources`` leaves (flat
    indices) to the results the rest of the program reads; ``step`` is
    the trace without the load's equations, over the ``kept`` leaves,
    those results and the inputs, in that order. ``narrowed`` are the
    sources whose conversion to a narrower floating dtype the load
    took over, with the converted copy's bytes; ``agrees`` is what two
    traces of one model share when their loads compute the same."""
    load: Any
    step: Any
    sources: Tuple[int, ...]
    kept: Tuple[int, ...]
    narrowed: Dict[int, int]

    @property
    def equations(self) -> int:
        return len(self.load.eqns)

    @property
    def agrees(self) -> Tuple:
        return self.sources, self.kept, str(self.load)

    def __bool__(self) -> bool:
        return bool(self.load.eqns)


def trace(jitted, params, xs) -> Tuple[Any, Any, Split]:
    """The one Python trace of ``jitted(params, *xs)``: its closed
    jaxpr, its output tree and :func:`split` of it. ``xs`` are arrays
    or ``ShapeDtypeStruct``s. ``jax.jit`` keeps the trace, so calling
    ``jitted`` afterwards on these very arguments does not run the
    model's Python again."""
    import jax
    traced = jitted.trace(params, *xs)
    return (traced.jaxpr, jax.tree.structure(traced.out_info),
            split(traced.jaxpr, len(jax.tree.leaves(params))))


# equations that only re-view their operand's elements
_VIEWS = ("reshape", "squeeze", "broadcast_in_dim")


def sub_jaxprs(eqn):
    """The programs nested in ``eqn``'s parameters: a loop's body, a
    branch, a ``jit``, a kernel."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(sub, ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, Jaxpr):
                yield sub


def split(closed, n_leaves: int) -> Split:
    """Cut ``closed``, whose first ``n_leaves`` inputs are the flattened
    parameters, into what those alone determine and the rest. An
    equation goes to the load when it has no effect and no sub-program,
    reads at least one leaf or result of the load, and nothing else but
    literals. A view (reshape, squeeze, broadcast of as many elements)
    that only the rest of the program reads stays with the rest: the
    compiled program reads it off its operand in place, and at the load
    it would only hold the operand twice. A load equation that nothing
    reads goes with neither."""
    import collections
    import jax
    import jax.numpy as jnp
    from jax.extend.core import Jaxpr, Var
    jaxpr = closed.jaxpr
    leaves = {v: i for i, v in enumerate(jaxpr.invars[:n_leaves])}

    def reads(eqn):
        return [v for v in eqn.invars if isinstance(v, Var)]

    known, moved = set(leaves), []
    for eqn in jaxpr.eqns:
        if reads(eqn) and known.issuperset(reads(eqn)) \
                and not eqn.effects and not any(sub_jaxprs(eqn)):
            moved.append(eqn)
            known.update(eqn.outvars)
    # last first, so that a chain of views unwinds
    readers = collections.Counter(v for eqn in moved for v in reads(eqn))
    for eqn in reversed(moved):
        if eqn.primitive.name in _VIEWS and not readers[eqn.outvars[0]] \
                and eqn.outvars[0].aval.size == eqn.invars[0].aval.size:
            known.discard(eqn.outvars[0])
            readers.subtract(reads(eqn))
    moved = [eqn for eqn in moved if known.issuperset(eqn.outvars)]
    gone = set(map(id, moved))
    rest = [eqn for eqn in jaxpr.eqns if id(eqn) not in gone]
    # what the rest of the program reads, in the order it first does
    read = dict.fromkeys(
        v for v in [v for eqn in rest for v in eqn.invars] + jaxpr.outvars
        if isinstance(v, Var) and v in known)
    results = [v for v in read if v not in leaves]
    needed, load_eqns = set(results), []
    for eqn in reversed(moved):
        if needed.intersection(eqn.outvars):
            load_eqns.append(eqn)
            needed.update(reads(eqn))
    load_eqns.reverse()
    sources = tuple(sorted(leaves[v] for v in needed if v in leaves))
    kept = tuple(sorted(leaves[v] for v in read if v in leaves))
    narrowed = {}
    for eqn in load_eqns:
        if eqn.primitive is not jax.lax.convert_element_type_p:
            continue
        src, dtype = eqn.invars[0], jnp.dtype(eqn.params["new_dtype"])
        if src in leaves and leaves[src] not in kept \
                and jnp.issubdtype(src.aval.dtype, jnp.floating) \
                and jnp.issubdtype(dtype, jnp.floating) \
                and dtype.itemsize < src.aval.dtype.itemsize:
            narrowed[leaves[src]] = narrowed.get(leaves[src], 0) \
                + src.aval.size * dtype.itemsize
    invars = list(jaxpr.invars)
    # whose names and paths are the whole trace's, not the halves'
    info = jaxpr.debug_info._replace(arg_names=None, result_paths=None)
    return Split(
        load=Jaxpr((), [invars[i] for i in sources], results, load_eqns,
                   debug_info=info),
        step=Jaxpr(jaxpr.constvars, [invars[i] for i in kept] + results
                   + invars[n_leaves:], jaxpr.outvars, rest, jaxpr.effects,
                   info),
        sources=sources, kept=kept, narrowed=narrowed)


def kernel_calls(closed) -> Dict[str, int]:
    """``{kernel name: call sites}`` of the Pallas kernels in the traced
    program, nested programs (loops, branches, ``jit``) included: what
    says that a model's kernel is on the path, read once off the trace
    and not off a device profile. Empty for a program in plain XLA."""
    found: Dict[str, int] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = str(eqn.params.get("name") or "pallas_call")
                found[name] = found.get(name, 0) + 1
                continue            # the kernel's own body is not a site
            for sub in sub_jaxprs(eqn):
                walk(sub)

    walk(closed.jaxpr)
    return found


def program(closed, out_tree, cut: Split) -> Callable:
    """``fn(held, *xs)`` evaluating the traced program without the
    load's equations, on ``held`` = the kept leaves then the load's
    results (:func:`load`); under ``split(closed, 0)`` that is the whole
    trace on every leaf. Only the flat jaxpr is walked when ``fn`` is
    jitted (or called inside a larger trace): the model's Python does
    not run again, and every equation keeps its ``named_scope``."""
    import jax

    def fn(held, *xs):
        out = jax.core.eval_jaxpr(cut.step, closed.consts, *held, *xs)
        return jax.tree.unflatten(out_tree, out)

    return fn


def load(cut: Split, leaves: Sequence[Any]) -> List[Any]:
    """``cut``'s results from the flattened parameters ``leaves``, by
    one device program (``jit_nns_filter_prepare`` in a trace). A result
    shaped as its one source leaf takes that leaf's sharding, so a
    mesh-placed tree stays placed; any other lies where the compiler
    puts it."""
    import jax
    from jax.extend.core import Var
    src = [leaves[i] for i in cut.sources]

    def nns_filter_prepare(*xs):
        return jax.core.eval_jaxpr(cut.load, (), *xs)

    # the source leaves each variable of the load is made of
    made_of = {v: {v} for v in cut.load.invars}
    for eqn in cut.load.eqns:
        of = set().union(*(made_of[v] for v in eqn.invars
                           if isinstance(v, Var)))
        made_of.update((out, of) for out in eqn.outvars)
    place = dict(zip(cut.load.invars, src))
    shardings = []
    for out in cut.load.outvars:
        one = next(iter(made_of[out]))
        shardings.append(place[one].sharding if len(made_of[out]) == 1
                         and one.aval.shape == out.aval.shape else None)
    return jax.jit(nns_filter_prepare, out_shardings=shardings)(*src)
