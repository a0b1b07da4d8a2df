"""Dense oracle of ``core.zo.tree_axpy`` for the tests: every leaf goes
through the reference ``kernels.ref.zo_axpy_nd`` in its natural shape,
each stacked leaf masked by the rows its slots enable."""
import jax
import jax.numpy as jnp

from repro.core import rng
from repro.kernels import ref


def slots_mask(slots, L):
    """(L,) bool of the rows a slot form enables: a static vector, or a
    (vector, enabled count) pair."""
    vec, n = slots if isinstance(slots, tuple) else (slots, slots.shape[0])
    rows = jnp.where(jnp.arange(vec.shape[0]) < n, vec, L)
    return jnp.zeros((L,), jnp.bool_).at[rows].set(True, mode="drop")


def tree_axpy(params, spec, seed, scale, idxs, *, decay=1.0):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = []
    for leaf, path, group in zip(leaves, spec.paths, spec.groups):
        leaf_seed = rng.fold(jnp.asarray(seed, jnp.uint32),
                             jnp.uint32(rng.leaf_uid(path)))
        if group is None:
            out.append(ref.zo_axpy_nd(leaf[None], None, leaf_seed, scale,
                                      decay)[0])
        else:
            out.append(ref.zo_axpy_nd(
                leaf, slots_mask(idxs[group], leaf.shape[0]), leaf_seed,
                scale, decay))
    return jax.tree_util.tree_unflatten(treedef, out)
