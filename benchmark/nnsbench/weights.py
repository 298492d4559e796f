"""Seeded weights, made on the device in one jitted call, in the type
they are served in. The seed is an argument of the compiled program, so
every seed hits the same cache entry."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key from a traced or concrete seed of up to 2**32 or so:
    the low 31 bits make the key, the rest is folded in."""
    seed = jnp.asarray(seed, jnp.uint32)
    key = jax.random.PRNGKey((seed & 0x7FFFFFFF).astype(jnp.int32))
    return jax.random.fold_in(key, seed >> 31)


def split_seed(seed: int):
    """A whole number of any size as a uint32 (wraps above 2**32)."""
    return jnp.asarray(int(seed) % (1 << 32), jnp.uint32)


def make_tree(shapes, rule, seed: int):
    """``shapes``: a pytree of ``jax.ShapeDtypeStruct``; ``rule(path,
    shape) -> (mean, std)`` by the leaf's path string. Each leaf is
    ``mean + std * u`` with ``u`` uniform of unit variance, drawn from
    the seed and the leaf's position."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = []
    for i, (path, sds) in enumerate(leaves):
        mean, std = rule(jax.tree_util.keystr(path), tuple(sds.shape))
        specs.append((i, tuple(sds.shape), sds.dtype, float(mean),
                      float(std)))

    def build(seed_u32):
        key = seed_key(seed_u32)
        out = []
        for i, shape, dtype, mean, std in specs:
            if std == 0.0:
                out.append(jnp.full(shape, mean, dtype))
                continue
            u = jax.random.uniform(jax.random.fold_in(key, i), shape,
                                   jnp.float32, -1.0, 1.0)
            out.append((mean + (std * 3.0 ** 0.5) * u).astype(dtype))
        return out

    made = jax.jit(build)(split_seed(seed))
    return jax.tree_util.tree_unflatten(treedef, made)
