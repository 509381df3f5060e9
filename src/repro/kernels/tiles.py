"""Shared tile helpers for the Pallas kernels: sequence-axis zero-padding to
a block multiple and the recurring BlockSpec shapes ((B, rows, d) row tiles,
(B, 1, d) per-example vectors, (B, 1, 1) scalars, (B, ns, 1, ·) per-tile
partials).
One definition so padding semantics cannot drift between kernels."""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl


def pad_rows(x, block_rows: int):
    """Zero-pad axis 1 of (B, S, ...) up to a multiple of ``block_rows``."""
    pad = (-x.shape[1]) % block_rows
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
    return jnp.pad(x, widths)


def pad_seq(x, target: int):
    """Zero-pad axis 2 of (B, H, S, hd) up to exactly ``target``."""
    S = x.shape[2]
    return x if S == target else jnp.pad(
        x, ((0, 0), (0, 0), (0, target - S), (0, 0)))


def row_spec(block_rows: int, d: int):
    return pl.BlockSpec((1, block_rows, d), lambda b, i: (b, i, 0))


# Mosaic tiles the LAST TWO dims of every block by (8, 128) unless a dim
# spans its whole array axis. Per-example vectors, scalars and per-tile
# partials therefore carry unit axes that ARE whole array axes: a (B, d)
# vector is passed as (B, 1, d), a (B,) scalar as (B, 1, 1), and (B, ns)
# partials as (B, ns, 1, 1) / (B, ns, 1, d).

def vec_spec(d: int):
    """(B, 1, d) per-example vector, broadcast over a row tile."""
    return pl.BlockSpec((1, 1, d), lambda b, i: (b, 0, 0))


def scalar_spec():
    """(B, 1, 1) per-example scalar."""
    return pl.BlockSpec((1, 1, 1), lambda b, i: (b, 0, 0))


def tile_spec():
    """(B, ns, 1, 1) one scalar per row tile."""
    return pl.BlockSpec((1, 1, 1, 1), lambda b, i: (b, i, 0, 0))


def partial_spec(d: int):
    """(B, ns, 1, d) one d-vector per row tile."""
    return pl.BlockSpec((1, 1, 1, d), lambda b, i: (b, i, 0, 0))
