"""Per-leaf norms of parameter-shaped trees, a stacked leaf split by layer
(``layers/attn/wq#4``), computed on the device in one jitted call."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import _is_stacked, _path_str


def _norms(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, x in flat:
        x = x.astype(jnp.float32)
        if _is_stacked(_path_str(path)):
            out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x)))
    return out


_norms_jit = jax.jit(_norms)


def name_norms(tree, values, offset: int = 0) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for (path, _x), v in zip(flat, values):
        p = _path_str(path)
        v = np.asarray(v, np.float64)
        if _is_stacked(p):
            for i, n in enumerate(v):
                out[f"{p}#{offset + i}"] = float(n)
        else:
            out[p] = float(v)
    return out


def leaf_norms(tree, offset: int = 0) -> Dict[str, float]:
    return name_norms(tree, jax.device_get(_norms_jit(tree)), offset)


_diff = jax.jit(lambda p, p0: _norms(jax.tree_util.tree_map(
    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0)))


def change_norms(params, params0) -> Dict[str, float]:
    """Norms of ``params - params0`` per leaf."""
    return name_norms(params, jax.device_get(_diff(params, params0)))
