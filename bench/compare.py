"""The gaps by which a run's numbers lie from the reference's."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is nought to rounding and leaves the change comparison
MOVED_SHARE = 1e-3


def slice_names(tree, stacked_key: str = "stack") -> list:
    """One name per compared leaf: a leaf under ``stacked_key`` is split
    along its leading (layer) axis, one slice per layer."""
    names = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if path and getattr(path[0], "key", None) == stacked_key:
            names += [f"{name}[{i}]" for i in range(leaf.shape[0])]
        else:
            names.append(name)
    return names


def slice_norms(tree, stacked_key: str = "stack"):
    """Float32 norm of every compared leaf, in :func:`slice_names` order
    (jit-able)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sq = jnp.square(leaf.astype(jnp.float32))
        if path and getattr(path[0], "key", None) == stacked_key:
            out.append(jnp.sqrt(jnp.sum(sq.reshape(sq.shape[0], -1), axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(sq))[None])
    return jnp.concatenate(out)


def norm_gap(prog, ref, keep=None) -> tuple[float, int]:
    """Worst leaf: max |‖prog‖ − ‖ref‖| / max(‖ref‖, median ‖ref‖) over the
    leaves in ``keep`` → (gap, index of that leaf)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else np.asarray(keep)
    med = float(np.median(ref[keep]))
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def moved(first_grad_norms) -> np.ndarray:
    """Leaves the reference's first gradient moves: norm at least
    :data:`MOVED_SHARE` of the median leaf's."""
    g = np.asarray(first_grad_norms, np.float64)
    return g >= MOVED_SHARE * float(np.median(g))


def rel_gap(prog, ref) -> float:
    """max |prog − ref| / |ref| elementwise."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def vec_gap(prog, ref) -> float:
    """max |prog − ref| / max |ref| over a vector."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref)) / max(np.max(np.abs(ref)),
                                                  1e-30))
