"""``models/latent.py``, the parts the scoring decoders share: each
decoder's tiny zoo default draws the seeded tree it drew before its
helpers were shared, leaf for leaf, and its expert layers still carry
the scope names the per-layer metrics read."""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import zoo

MOE = {"block/mlp", "block/moe/route", "block/moe/experts",
       "block/moe/experts/rows", "block/moe/experts/nns_grouped_swiglu"}


def _digest(params):
    """sha256 over every leaf: its path, dtype, shape and bytes."""
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(params)
    for path, leaf in leaves:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return len(leaves), h.hexdigest()[:16]


# the digests as the decoders drew their trees before the helpers were
# shared (each with its own key counter and dense / ones / ffn)
@pytest.mark.parametrize("name,leaves,digest,scopes", [
    ("glm_dsa", 64, "1bca179556bf6dc2", MOE | {"block/moe/shared"}),
    ("longcat", 61, "13b5a4851a024798", MOE | {"block/moe/zero"}),
    ("afmoe", 74, "a0055c9dffa1ac1b", MOE | {"block/moe/shared"}),
    ("kimi_linear", 113, "df4b75d6fec87bbd", MOE | {"block/moe/shared"}),
    ("brumby", 29, "127faf917874eeaa", {"block/mlp"}),
])
def test_the_seeded_tree_and_the_scopes_stay(name, leaves, digest, scopes):
    apply_fn, params, in_info, _, *state = zoo.build(name, seed="0")
    assert _digest(params) == (leaves, digest)
    closed = jax.make_jaxpr(apply_fn)(params, *state, *(
        jnp.zeros(i.shape, i.type.np_dtype) for i in in_info))
    found = {str(e.source_info.name_stack) for e in closed.jaxpr.eqns}
    assert {n for n in found if n.startswith(("block/moe", "block/mlp"))} \
        == scopes
