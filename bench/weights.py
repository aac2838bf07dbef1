"""Random weights drawn from the seed, on the device, in one program.

The layout is the reference's (`param_shapes` of the architecture),
which is also the layout the program takes; `check_layout` holds the
two to each other before a run.  Every leaf has a key of its own,
folded from the seed and the leaf's index, so a leaf does not depend on
the others.  Scales: those the architecture's `draw_scale(path, shape)`
gives, where it has one and gives one; otherwise, by the leaf's name,
matrices 1/sqrt(fan-in), embeddings and biases 0.02, norm scales
1 + 0.1 N(0, 1), so that no parameter is a constant that would hide a
term left out.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.model import arch

F32 = jnp.float32


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key for any seed up to 64 bits."""
    seed = int(seed) & (2 ** 64 - 1)
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _key(k):
    """A path element: a dict's key, or a list's index (an int)."""
    return k.idx if isinstance(k, jax.tree_util.SequenceKey) else k.key


def _paths(tree) -> tuple:
    """(path, shape) of each leaf of a tree of shapes, dicts in sorted
    key order and lists by index, with the tree's structure."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    return [(tuple(map(_key, path)), s) for path, s in leaves], treedef


def _draw(a, key, path, shape):
    z = jax.random.normal(key, shape, F32)
    scale = a.draw_scale(path, shape) if hasattr(a, "draw_scale") else None
    if scale is not None:
        return scale * z
    name = path[-1]
    if name == "w":
        return z / np.sqrt(shape[-2])
    if name == "scale":
        return 1.0 + 0.1 * z
    return 0.02 * z        # embedding table, biases, norm biases


def make_fn(cfg: dict):
    """key -> params, compiled once per configuration."""
    return _make_fn(json.dumps(cfg, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _make_fn(cfg_json: str):
    cfg = json.loads(cfg_json)
    a = arch(cfg)
    leaves, treedef = _paths(a.param_shapes(cfg))

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(a, jax.random.fold_in(key, i), path, shape)
            for i, (path, shape) in enumerate(leaves)])

    return jax.jit(build)


def check_layout(cfg: dict, program_shapes) -> None:
    """Raise unless the program's parameter tree has the reference's
    leaves, shapes and float32 storage."""
    want = dict(_paths(arch(cfg).param_shapes(cfg))[0])
    got = {tuple(map(_key, path)): leaf
           for path, leaf in jax.tree_util.tree_leaves_with_path(
               program_shapes)}
    if set(want) != set(got):
        only_ref = sorted(set(want) - set(got), key=str)
        only_prog = sorted(set(got) - set(want), key=str)
        raise ValueError(f"parameter layout differs: only in the reference "
                         f"{only_ref}, only in the program {only_prog}")
    for path, shape in want.items():
        leaf = got[path]
        if tuple(leaf.shape) != shape or leaf.dtype != F32:
            raise ValueError(f"{'/'.join(map(str, path))}: program has "
                             f"{leaf.shape} {leaf.dtype}, reference {shape} "
                             "float32")
