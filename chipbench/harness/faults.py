"""Faults planted underneath the program from outside its job, for those
``run.py --fault`` does not name (``plant.py`` runs a cell with one).

``psum_left_out``: every ``jax.lax.psum`` the program traces inside returns
its own operand, so in block-parallel training each chip updates the
periphery with its own block's gradient alone: the exchange between chips
is left out, and the replicas of the periphery drift apart."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def psum_left_out():
    import jax
    real = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **kw: x
    try:
        yield
    finally:
        jax.lax.psum = real


PLANTS = {"psum_left_out": psum_left_out}
