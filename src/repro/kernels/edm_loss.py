"""Fused EDM denoising loss kernel (paper Eq. 2/6, F-space form).

Computes per-tile partial sums of ||F − (y − c_skip z)/c_out||² without
materializing the target tensor in HBM: each (block_rows × d) tile of F, z, y
is read once, the target is formed in VMEM, squared error reduced on the VPU,
and one partial scalar per tile is written out. The caller sums the partials
(a (grid,) vector) — O(B·S/block_rows) bytes instead of O(B·S·d).

Differentiable via ``jax.custom_vjp``: the VJP is one cheap elementwise
kernel that re-forms the target in VMEM and scales by the incoming per-tile
cotangent — the target STILL never rematerializes in HBM on either pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import (pad_rows as _pad3, row_spec as _rows_spec,
                                 scalar_spec as _scalar_spec, tile_spec
                                 as _tile_spec)

BLOCK_ROWS = 256


def _coeffs(sigma, sigma_data: float):
    """c_skip/c_out per EDM preconditioning — pinned against
    core/edm.preconditioning by tests/test_kernel_grads.py (kernels stay
    import-light; the test makes silent drift impossible)."""
    B = sigma.shape[0]
    sf = sigma.astype(jnp.float32)
    s2 = sf ** 2
    d2 = sigma_data ** 2
    c_skip = (d2 / (s2 + d2)).reshape(B, 1)
    c_out = (sf * sigma_data * jax.lax.rsqrt(s2 + d2)).reshape(B, 1)
    return c_skip, c_out


def _loss_kernel(f_ref, z_ref, y_ref, cs_ref, co_ref, o_ref, *, rows: int,
                 block_rows: int):
    i = pl.program_id(1)
    f = f_ref[0].astype(jnp.float32)
    z = z_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    c_skip = cs_ref[0]                                   # (1, 1)
    c_out = co_ref[0]
    target = (y - c_skip * z) / c_out
    err = jnp.square(f - target)
    # zero padded rows
    ridx = i * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, err.shape, 0)
    err = jnp.where(ridx < rows, err, 0.0)
    o_ref[0, 0] = jnp.sum(err, keepdims=True)


def _loss_bwd_kernel(f_ref, z_ref, y_ref, cs_ref, co_ref, g_ref,
                     df_ref, dz_ref, dy_ref, *, rows: int, block_rows: int):
    """err = (f − t)², t = (y − c_skip z)/c_out ⇒ per-element
    df = 2(f−t)·g,  dz = (c_skip/c_out)·df,  dy = −df/c_out."""
    i = pl.program_id(1)
    f = f_ref[0].astype(jnp.float32)
    z = z_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    c_skip = cs_ref[0]                                   # (1, 1)
    c_out = co_ref[0]
    g = g_ref[0, 0]                                      # (1, 1) tile cotangent
    target = (y - c_skip * z) / c_out
    df = 2.0 * (f - target) * g
    ridx = i * block_rows + jax.lax.broadcasted_iota(jnp.int32, df.shape, 0)
    df = jnp.where(ridx < rows, df, 0.0)
    df_ref[0] = df.astype(df_ref.dtype)
    dz_ref[0] = (df * (c_skip / c_out)).astype(dz_ref.dtype)
    dy_ref[0] = (-df / c_out).astype(dy_ref.dtype)


def _partials_fwd_call(f, z, y, c_skip, c_out, rows, block_rows, interpret):
    B, _, d = f.shape
    fp, zp, yp = (_pad3(t, block_rows) for t in (f, z, y))
    ns = fp.shape[1] // block_rows
    partials = pl.pallas_call(
        functools.partial(_loss_kernel, rows=rows, block_rows=block_rows),
        grid=(B, ns),
        in_specs=[_rows_spec(block_rows, d)] * 3 + [_scalar_spec()] * 2,
        out_specs=_tile_spec(),
        out_shape=jax.ShapeDtypeStruct((B, ns, 1, 1), jnp.float32),
        interpret=interpret,
        name="edm_loss_fwd",
    )(fp, zp, yp, c_skip.reshape(B, 1, 1), c_out.reshape(B, 1, 1))
    return partials.reshape(B, ns)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _partials(f, z, y, sigma, sigma_data, block_rows, interpret):
    c_skip, c_out = _coeffs(sigma, sigma_data)
    return _partials_fwd_call(f, z, y, c_skip, c_out, f.shape[1],
                              block_rows, interpret)


def _partials_vjp_fwd(f, z, y, sigma, sigma_data, block_rows, interpret):
    c_skip, c_out = _coeffs(sigma, sigma_data)
    out = _partials_fwd_call(f, z, y, c_skip, c_out, f.shape[1],
                             block_rows, interpret)
    return out, (f, z, y, c_skip, c_out, sigma)


def _partials_vjp_bwd(sigma_data, block_rows, interpret, res, g):
    f, z, y, c_skip, c_out, sigma = res
    B, S, d = f.shape
    fp, zp, yp = (_pad3(t, block_rows) for t in (f, z, y))
    ns = fp.shape[1] // block_rows
    df, dz, dy = pl.pallas_call(
        functools.partial(_loss_bwd_kernel, rows=S, block_rows=block_rows),
        grid=(B, ns),
        in_specs=[_rows_spec(block_rows, d)] * 3 + [_scalar_spec()] * 2
        + [_tile_spec()],
        out_specs=[_rows_spec(block_rows, d)] * 3,
        out_shape=[jax.ShapeDtypeStruct(fp.shape, f.dtype),
                   jax.ShapeDtypeStruct(fp.shape, z.dtype),
                   jax.ShapeDtypeStruct(fp.shape, y.dtype)],
        interpret=interpret,
        name="edm_loss_bwd",
    )(fp, zp, yp, c_skip.reshape(B, 1, 1), c_out.reshape(B, 1, 1),
      g.astype(jnp.float32).reshape(B, ns, 1, 1))
    # σ parameterizes the sampled noise level — never differentiated
    return df[:, :S], dz[:, :S], dy[:, :S], jnp.zeros_like(sigma)


_partials.defvjp(_partials_vjp_fwd, _partials_vjp_bwd)


def edm_loss_partials(f: jax.Array, z: jax.Array, y: jax.Array,
                      sigma: jax.Array, sigma_data: float,
                      block_rows: int = BLOCK_ROWS,
                      interpret: bool = False) -> jax.Array:
    """f/z/y: (B, S, d); sigma: (B,). Returns partial sums (B, n_tiles);
    loss = sum(partials) / (B*S*d). Differentiable w.r.t. f, z, y."""
    block_rows = min(block_rows, f.shape[1])
    return _partials(f, z, y, sigma, sigma_data, block_rows, interpret)


def edm_loss(f, z, y, sigma, sigma_data: float, interpret: bool = False):
    B, S, d = f.shape
    partials = edm_loss_partials(f, z, y, sigma, sigma_data,
                                 interpret=interpret)
    return jnp.sum(partials) / (B * S * d)
