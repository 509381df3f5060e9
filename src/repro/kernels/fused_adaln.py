"""Fused AdaLN kernels — the elementwise hot path DiffusionBlocks adds to
every layer (noise conditioning, paper §3.1 Step 3).

Unfused, each layer costs 4 extra HBM round-trips of the (tokens, d) stream:
LN read/write, modulate read/write, gate read/write, residual read/write.
The two kernels here keep a (block_rows × d) tile resident in VMEM:

  fused_ln_modulate:  out = LN(x) * (1 + scale) + shift        (one pass)
  fused_gate_residual: out = res + branch * (1 + gate)          (one pass)

and a third fuses the EDM denoiser combine with the Euler step (Eq. 5):

  fused_euler: z' = (r + (1-r)·c_skip) · z + (1-r)·c_out · f

scale/shift/gate are per-example (B, d) vectors (σ-conditioning), broadcast
over the row tile.

All three are differentiable via ``jax.custom_vjp`` backed by Pallas backward
kernels: the backward pass reads each tile once, recomputes the cheap
row statistics in VMEM, and emits per-tile partial sums for the (B, d)
conditioning gradients (summed by the caller — O(B·n_tiles·d) bytes, no
atomics needed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import (pad_rows as _pad_rows, partial_spec
                                 as _partial_spec, row_spec as _row_specs,
                                 scalar_spec, vec_spec as _vec_spec)

BLOCK_ROWS = 256


# ---------------------------------------------------------------------------
# fused_ln_modulate: out = LN(x) * (1 + scale) + shift
# ---------------------------------------------------------------------------

def _ln_mod_kernel(x_ref, scale_ref, shift_ref, o_ref, *, eps: float):
    x = x_ref[0].astype(jnp.float32)                       # (rows, d)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    y = y * (1.0 + scale_ref[0].astype(jnp.float32)) \
        + shift_ref[0].astype(jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


def _ln_mod_bwd_kernel(x_ref, scale_ref, g_ref, dx_ref, dsc_ref, dsh_ref, *,
                       eps: float):
    """LN backward with the normalization stats recomputed in VMEM:
    dx = rstd · (dy − mean_d(dy) − x̂ · mean_d(dy·x̂)), dy = g·(1+scale);
    per-tile partials dscale = Σ_rows g·x̂, dshift = Σ_rows g."""
    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    scale = scale_ref[0].astype(jnp.float32)               # (1, d) broadcast
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    dy = g * (1.0 + scale)
    dx = rstd * (dy - jnp.mean(dy, axis=-1, keepdims=True)
                 - xhat * jnp.mean(dy * xhat, axis=-1, keepdims=True))
    dx_ref[0] = dx.astype(dx_ref.dtype)
    dsc_ref[0, 0] = jnp.sum(g * xhat, axis=0, keepdims=True)
    dsh_ref[0, 0] = jnp.sum(g, axis=0, keepdims=True)


def _ln_mod_fwd_call(x, scale, shift, eps, block_rows, interpret):
    B, S, d = x.shape
    block_rows = min(block_rows, S)
    xp = _pad_rows(x, block_rows)
    ns = xp.shape[1] // block_rows
    out = pl.pallas_call(
        functools.partial(_ln_mod_kernel, eps=eps),
        grid=(B, ns),
        in_specs=[_row_specs(block_rows, d), _vec_spec(d), _vec_spec(d)],
        out_specs=_row_specs(block_rows, d),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        interpret=interpret,
        name="fused_ln_modulate_fwd",
    )(xp, scale.reshape(B, 1, d), shift.reshape(B, 1, d))
    return out[:, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln_mod(x, scale, shift, eps, block_rows, interpret):
    return _ln_mod_fwd_call(x, scale, shift, eps, block_rows, interpret)


def _ln_mod_vjp_fwd(x, scale, shift, eps, block_rows, interpret):
    return (_ln_mod_fwd_call(x, scale, shift, eps, block_rows, interpret),
            (x, scale))


def _ln_mod_vjp_bwd(eps, block_rows, interpret, res, g):
    x, scale = res
    B, S, d = x.shape
    block_rows = min(block_rows, S)
    xp = _pad_rows(x, block_rows)
    gp = _pad_rows(g, block_rows)          # zero rows ⇒ zero partials
    ns = xp.shape[1] // block_rows
    dx, dsc, dsh = pl.pallas_call(
        functools.partial(_ln_mod_bwd_kernel, eps=eps),
        grid=(B, ns),
        in_specs=[_row_specs(block_rows, d), _vec_spec(d),
                  _row_specs(block_rows, d)],
        out_specs=[_row_specs(block_rows, d), _partial_spec(d),
                   _partial_spec(d)],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, ns, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, ns, 1, d), jnp.float32)],
        interpret=interpret,
        name="fused_ln_modulate_bwd",
    )(xp, scale.reshape(B, 1, d), gp)
    dscale = dsc.sum(axis=(1, 2)).astype(scale.dtype)
    dshift = dsh.sum(axis=(1, 2)).astype(scale.dtype)
    return dx[:, :S], dscale, dshift


_ln_mod.defvjp(_ln_mod_vjp_fwd, _ln_mod_vjp_bwd)


def fused_ln_modulate(x: jax.Array, scale: jax.Array, shift: jax.Array,
                      eps: float = 1e-6, block_rows: int = BLOCK_ROWS,
                      interpret: bool = False) -> jax.Array:
    """x: (B, S, d); scale/shift: (B, d). Non-parametric LN + AdaLN affine."""
    return _ln_mod(x, scale, shift, eps, block_rows, interpret)


# ---------------------------------------------------------------------------
# fused_gate_residual: out = res + branch * (1 + gate)
# ---------------------------------------------------------------------------

def _gate_res_kernel(res_ref, br_ref, gate_ref, o_ref):
    o_ref[0] = (res_ref[0].astype(jnp.float32)
                + br_ref[0].astype(jnp.float32)
                * (1.0 + gate_ref[0].astype(jnp.float32))).astype(o_ref.dtype)


def _gate_res_bwd_kernel(br_ref, gate_ref, g_ref, dbr_ref, dg_ref):
    br = br_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    dbr_ref[0] = (g * (1.0 + gate_ref[0].astype(jnp.float32))
                  ).astype(dbr_ref.dtype)
    dg_ref[0, 0] = jnp.sum(g * br, axis=0, keepdims=True)


def _gate_res_fwd_call(res, branch, gate, block_rows, interpret):
    B, S, d = res.shape
    block_rows = min(block_rows, S)
    rp = _pad_rows(res, block_rows)
    bp = _pad_rows(branch, block_rows)
    ns = rp.shape[1] // block_rows
    out = pl.pallas_call(
        _gate_res_kernel,
        grid=(B, ns),
        in_specs=[_row_specs(block_rows, d), _row_specs(block_rows, d),
                  _vec_spec(d)],
        out_specs=_row_specs(block_rows, d),
        out_shape=jax.ShapeDtypeStruct(rp.shape, res.dtype),
        interpret=interpret,
        name="fused_gate_residual_fwd",
    )(rp, bp, gate.reshape(B, 1, d))
    return out[:, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gate_res(res, branch, gate, block_rows, interpret):
    return _gate_res_fwd_call(res, branch, gate, block_rows, interpret)


def _gate_res_vjp_fwd(res, branch, gate, block_rows, interpret):
    return (_gate_res_fwd_call(res, branch, gate, block_rows, interpret),
            (branch, gate))


def _gate_res_vjp_bwd(block_rows, interpret, res, g):
    branch, gate = res
    B, S, d = branch.shape
    block_rows = min(block_rows, S)
    bp = _pad_rows(branch, block_rows)
    gp = _pad_rows(g, block_rows)
    ns = bp.shape[1] // block_rows
    dbr, dg = pl.pallas_call(
        _gate_res_bwd_kernel,
        grid=(B, ns),
        in_specs=[_row_specs(block_rows, d), _vec_spec(d),
                  _row_specs(block_rows, d)],
        out_specs=[_row_specs(block_rows, d), _partial_spec(d)],
        out_shape=[jax.ShapeDtypeStruct(bp.shape, branch.dtype),
                   jax.ShapeDtypeStruct((B, ns, 1, d), jnp.float32)],
        interpret=interpret,
        name="fused_gate_residual_bwd",
    )(bp, gate.reshape(B, 1, d), gp)
    dgate = dg.sum(axis=(1, 2)).astype(gate.dtype)
    return g, dbr[:, :S], dgate        # d res = identity pass-through


_gate_res.defvjp(_gate_res_vjp_fwd, _gate_res_vjp_bwd)


def fused_gate_residual(res: jax.Array, branch: jax.Array, gate: jax.Array,
                        block_rows: int = BLOCK_ROWS,
                        interpret: bool = False) -> jax.Array:
    """res/branch: (B, S, d); gate: (B, d)."""
    return _gate_res(res, branch, gate, block_rows, interpret)


# ---------------------------------------------------------------------------
# fused_euler: z' = (r + (1-r) c_skip) z + (1-r) c_out f
# ---------------------------------------------------------------------------

def _euler_kernel(z_ref, f_ref, a_ref, b_ref, o_ref):
    a = a_ref[0]                                  # (1, 1) scalars per example
    b = b_ref[0]
    o_ref[0] = (a * z_ref[0].astype(jnp.float32)
                + b * f_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def _euler_bwd_kernel(g_ref, a_ref, b_ref, dz_ref, df_ref):
    g = g_ref[0].astype(jnp.float32)
    dz_ref[0] = (a_ref[0] * g).astype(dz_ref.dtype)
    df_ref[0] = (b_ref[0] * g).astype(df_ref.dtype)


def _euler_coeffs(sigma, sigma_to, sigma_data: float):
    """EDM preconditioning folded into the Euler combine — pinned against
    core/edm.preconditioning by tests/test_kernel_grads.py."""
    B = sigma.shape[0]
    sf = sigma.astype(jnp.float32)
    s2 = sf ** 2
    d2 = sigma_data ** 2
    c_skip = d2 / (s2 + d2)
    c_out = sf * sigma_data * jax.lax.rsqrt(s2 + d2)
    r = sigma_to.astype(jnp.float32) / sf
    a = (r + (1 - r) * c_skip).reshape(B, 1)
    b = ((1 - r) * c_out).reshape(B, 1)
    return a, b


def _euler_fwd_call(z, f, a, b, block_rows, interpret):
    B, S, d = z.shape
    block_rows = min(block_rows, S)
    zp = _pad_rows(z, block_rows)
    fp = _pad_rows(f, block_rows)
    ns = zp.shape[1] // block_rows
    out = pl.pallas_call(
        _euler_kernel,
        grid=(B, ns),
        in_specs=[_row_specs(block_rows, d), _row_specs(block_rows, d),
                  scalar_spec(), scalar_spec()],
        out_specs=_row_specs(block_rows, d),
        out_shape=jax.ShapeDtypeStruct(zp.shape, z.dtype),
        interpret=interpret,
        name="fused_euler_fwd",
    )(zp, fp, a.reshape(B, 1, 1), b.reshape(B, 1, 1))
    return out[:, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _euler(z, f, sigma, sigma_to, sigma_data, block_rows, interpret):
    a, b = _euler_coeffs(sigma, sigma_to, sigma_data)
    return _euler_fwd_call(z, f, a, b, block_rows, interpret)


def _euler_vjp_fwd(z, f, sigma, sigma_to, sigma_data, block_rows, interpret):
    a, b = _euler_coeffs(sigma, sigma_to, sigma_data)
    out = _euler_fwd_call(z, f, a, b, block_rows, interpret)
    return out, (a, b, sigma, sigma_to)


def _euler_vjp_bwd(sigma_data, block_rows, interpret, res, g):
    a, b, sigma, sigma_to = res
    B, S, d = g.shape
    block_rows = min(block_rows, S)
    gp = _pad_rows(g, block_rows)
    ns = gp.shape[1] // block_rows
    dz, df = pl.pallas_call(
        _euler_bwd_kernel,
        grid=(B, ns),
        in_specs=[_row_specs(block_rows, d), scalar_spec(), scalar_spec()],
        out_specs=[_row_specs(block_rows, d), _row_specs(block_rows, d)],
        out_shape=[jax.ShapeDtypeStruct(gp.shape, g.dtype),
                   jax.ShapeDtypeStruct(gp.shape, g.dtype)],
        interpret=interpret,
        name="fused_euler_bwd",
    )(gp, a.reshape(B, 1, 1), b.reshape(B, 1, 1))
    # σ is sampled noise-schedule data, never a learnable input — zero cotangent
    return dz[:, :S], df[:, :S], jnp.zeros_like(sigma), jnp.zeros_like(sigma_to)


_euler.defvjp(_euler_vjp_fwd, _euler_vjp_bwd)


def fused_euler(z: jax.Array, f: jax.Array, sigma: jax.Array,
                sigma_to: jax.Array, sigma_data: float,
                block_rows: int = BLOCK_ROWS,
                interpret: bool = False) -> jax.Array:
    """Fused denoise-combine + Euler step (paper Eq. 5 with EDM
    parameterization):  D = c_skip z + c_out F,  z' = r z + (1-r) D
    ⇒ z' = (r + (1-r) c_skip) z + (1-r) c_out F.

    z/f: (B, S, d); sigma/sigma_to: (B,) per-example noise levels."""
    return _euler(z, f, sigma, sigma_to, sigma_data, block_rows, interpret)
