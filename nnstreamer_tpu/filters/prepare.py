"""Parameters in their compute dtype, converted once per load.

A flax module keeps float32 leaves and converts each to its compute
dtype where it uses it, so the jitted ``apply_fn(params, *inputs)``
converts every kernel inside the device program, once per buffer (for
ViT-H/14: 2.5 GB of float32 on the vector unit inside the matmul
fusions). The values are constants between model loads, and rounding a
float32 to bfloat16 gives the same bits whenever it is done.

So the jax filter reads, off the one trace it makes of the model, the
parameter leaves whose EVERY use is a ``convert_element_type`` to one
and the same narrower floating dtype (:func:`narrowable`), converts
those once on the device (:func:`convert`), and runs the traced program
with the conversions taken out (:func:`program`) on the converted tree.
Nothing here knows a model: a leaf that is used in float32 anywhere, or
is already in its compute dtype, is left alone, and a program in which
no leaf qualifies is not touched at all.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple


def trace(jitted, params, xs) -> Tuple[Any, Any, Dict[int, Any]]:
    """The one Python trace of ``jitted(params, *xs)``: its closed
    jaxpr, its output tree and :func:`narrowable` of it. ``xs`` are
    arrays or ``ShapeDtypeStruct``s. ``jax.jit`` keeps the trace, so
    calling ``jitted`` afterwards on these very arguments does not run
    the model's Python again."""
    import jax
    traced = jitted.trace(params, *xs)
    return (traced.jaxpr, jax.tree.structure(traced.out_info),
            narrowable(traced.jaxpr, len(jax.tree.leaves(params))))


def narrowable(closed, n_leaves: int) -> Dict[int, Any]:
    """``{leaf index: dtype}`` over the first ``n_leaves`` inputs of
    ``closed`` (the flattened parameters): the leaves whose every use in
    the program is a plain ``convert_element_type`` to that one floating
    dtype, narrower than their own. A leaf handed to any other equation
    (a sub-program's operand included) or returned does not qualify."""
    import jax
    import jax.numpy as jnp
    from jax.extend.core import Var
    jaxpr = closed.jaxpr
    index = {v: i for i, v in enumerate(jaxpr.invars[:n_leaves])}
    target: Dict[int, Any] = {}       # None once a use disqualifies
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


def kernel_calls(closed) -> Dict[str, int]:
    """``{kernel name: call sites}`` of the Pallas kernels in the traced
    program, nested programs (loops, branches, ``jit``) included: what
    says that a model's kernel is on the path, read once off the trace
    and not off a device profile. Empty for a program in plain XLA."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    found: Dict[str, int] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = str(eqn.params.get("name") or "pallas_call")
                found[name] = found.get(name, 0) + 1
                continue            # the kernel's own body is not a site
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)

    walk(closed.jaxpr)
    return found


def _narrowed(closed, narrow: Dict[int, Any]):
    """``closed`` taking the ``narrow`` leaves in their target dtype:
    those inputs retyped, their conversions dropped, each conversion's
    result read from the input itself."""
    from jax.extend.core import ClosedJaxpr, Var
    jaxpr = closed.jaxpr
    invars = list(jaxpr.invars)
    sub = {}
    for i, dtype in narrow.items():
        new = Var(invars[i].aval.update(dtype=dtype))
        sub[invars[i]] = invars[i] = new

    def read(v):
        return sub.get(v, v) if isinstance(v, Var) else v

    leaves = {jaxpr.invars[i] for i in narrow}
    eqns = []
    for eqn in jaxpr.eqns:
        if eqn.invars and isinstance(eqn.invars[0], Var) \
                and eqn.invars[0] in leaves:
            sub[eqn.outvars[0]] = sub[eqn.invars[0]]
        else:
            eqns.append(eqn.replace(invars=[read(v) for v in eqn.invars]))
    return ClosedJaxpr(
        jaxpr.replace(invars=invars, eqns=eqns,
                      outvars=[read(v) for v in jaxpr.outvars]),
        closed.consts)


def program(closed, out_tree, narrow: Dict[int, Any]) -> Callable:
    """``fn(tree, *xs)`` evaluating the traced program, without its
    conversions of the ``narrow`` leaves, on a tree that holds those
    leaves converted. Only the flat jaxpr is walked when ``fn`` is
    jitted (or called inside a larger trace): the model's Python does
    not run again, and every equation keeps its ``named_scope``."""
    import jax
    closed = _narrowed(closed, narrow) if narrow else closed

    def fn(tree, *xs):
        out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts,
                                  *jax.tree.leaves(tree), *xs)
        return jax.tree.unflatten(out_tree, out)

    return fn


def convert(leaves: Sequence[Any], dtypes: Sequence[Any]) -> List[Any]:
    """``leaves`` in ``dtypes``, by one device program
    (``jit_nns_filter_prepare`` in a trace); each result takes its
    source leaf's sharding, so a mesh-placed tree stays placed."""
    import jax

    def nns_filter_prepare(*xs):
        return [x.astype(d) for x, d in zip(xs, dtypes)]

    return jax.jit(nns_filter_prepare,
                   out_shardings=[x.sharding for x in leaves])(*leaves)
