"""Weights from the seed, made by the benchmark and not by the program.

Every leaf is named by its path in the parameter tree (``layers/attn/wq``)
and drawn from a key folded from the seed, the path and, for the stacked
per-layer leaves, the layer index. So the program's weights and the plain
reference's are the same numbers, and the reference can draw one layer at
a time. ``make_tree`` draws a whole tree on the device in one jitted call,
in the dtype it is served or trained in; the seed enters as data, so one
compiled program serves every seed.

Init rule (per leaf, by name): embedding rows 0.02·N(0,1); norm gains
1 + 0.1·N; biases 0.02·N; the AdaLN modulation head 0.1·N/sqrt(fan_in)
(non-zero, so the σ-conditioning path is exercised: the program's own init
zeroes it); every other matrix N/sqrt(fan_in)."""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

STACKED = ("layers",)      # top-level keys whose leaves carry a layer axis


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key (uint32[2]) for any whole-number seed."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _scale(path: str, shape) -> tuple:
    """(kind, std) for a per-layer leaf shape."""
    name = path.rsplit("/", 1)[-1]
    if name == "table":
        return "normal", 0.02
    if name == "g":
        return "one_plus", 0.1
    if name == "b":
        return "normal", 0.02
    fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else int(shape[0])
    std = 1.0 / math.sqrt(max(fan_in, 1))
    if path.endswith("adaln/w"):
        std *= 0.1
    return "normal", std


def leaf(key, path: str, shape, layer=None):
    """One per-layer leaf (fp32). ``layer`` is the index along the stacked
    axis (traced is fine), or None for an unstacked leaf."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    kind, std = _scale(path, shape)
    x = jax.random.normal(k, shape, jnp.float32) * std
    return 1.0 + x if kind == "one_plus" else x


def _is_stacked(path: str) -> bool:
    return path.split("/", 1)[0] in STACKED


def make_fn(shapes, dtype):
    """A function key -> tree of ``shapes`` (a pytree of arrays or
    ShapeDtypeStructs) with every leaf drawn by ``leaf`` and cast to
    ``dtype``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fn(key):
        out = []
        for path, s in flat:
            p = _path_str(path)
            if _is_stacked(p):
                n = s.shape[0]
                x = jax.vmap(lambda i: leaf(key, p, s.shape[1:], i))(
                    jnp.arange(n))
            else:
                x = leaf(key, p, s.shape)
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)
    return fn


_MAKERS = {}


def maker(shapes, dtype, out_shardings=None):
    """The jitted drawing program for a tree, one per (layout, dtype): the
    same compiled program draws the tree again bit for bit."""
    key = (tuple(sorted(layout(shapes).items())), jnp.dtype(dtype).name,
           out_shardings is None)
    if key not in _MAKERS:
        _MAKERS[key] = jax.jit(make_fn(shapes, dtype),
                               out_shardings=out_shardings)
    return _MAKERS[key]


def make_tree(shapes, seed: int, dtype, out_shardings=None):
    """Draw the whole tree on the device in one jitted call."""
    return maker(shapes, dtype, out_shardings)(seed_key(seed))


def layout(shapes) -> dict:
    """{path: shape} of a tree, per-layer shapes with the layer count
    first for stacked leaves — what the reference's own layout must equal."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {_path_str(p): tuple(s.shape) for p, s in flat}


def shapes_of(lay: dict, dtype=jnp.float32) -> dict:
    """The nested tree of ShapeDtypeStructs for a ``{path: shape}``
    layout (the inverse of ``layout``)."""
    tree: dict = {}
    for path, shp in lay.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jax.ShapeDtypeStruct(shp, dtype)
    return tree
