"""Gradient-correctness suite for the custom-VJP Pallas kernels.

Every kernel's hand-written backward (interpret mode on CPU) is checked
against ``jax.grad`` of the pure-jnp oracle in ``kernels/ref.py`` — fp32 to
tight tolerance, bf16 inputs (fp32 accumulation inside the kernels) to a
loose one — including odd / padded sequence lengths and the DB-specific mask
kinds. A final end-to-end check runs ``make_db_train_step``'s loss with
``impl="kernels"`` vs the chunked reference path and compares full param
gradients (ISSUE 2 acceptance: ≤1e-4 rel-err in fp32).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.edm_loss import edm_loss
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_adaln import (fused_euler, fused_gate_residual,
                                       fused_ln_modulate)

DTYPES = [jnp.float32, jnp.bfloat16]


def gtol(dtype):
    # bf16 inputs round q/k/v and the cotangent to 8 mantissa bits, but the
    # kernels accumulate in fp32 — 4e-2 relative covers the input rounding.
    return 4e-2 if dtype == jnp.bfloat16 else 1e-5


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)


def check_grads(f_ker, f_ref, args, tol, argnums=None):
    argnums = tuple(range(len(args))) if argnums is None else argnums
    gk = jax.grad(f_ker, argnums=argnums)(*args)
    gr = jax.grad(f_ref, argnums=argnums)(*args)
    for i, (a, b) in enumerate(zip(gk, gr)):
        assert rel_err(a, b) < tol, f"arg {argnums[i]}: rel err {rel_err(a, b)}"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,H,KV,Sq,Sk,hd", [
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 128, 128, 32),     # GQA: dk/dv group-sum path
    (1, 4, 1, 91, 175, 32),      # MQA, odd/ragged (padding path)
    (1, 4, 2, 192, 192, 32),     # GQA; causal skips tiles, full and partial
    (2, 2, 2, 200, 180, 32),     # Sq > Sk, both padded: a partial tail
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                           (False, None)])
def test_flash_attention_grads(B, H, KV, Sq, Sk, hd, dtype, causal, window):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, H, Sq, hd), dtype)
    k = jax.random.normal(k2, (B, KV, Sk, hd), dtype)
    v = jax.random.normal(k3, (B, KV, Sk, hd), dtype)

    def f_ker(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, window=window, block_q=64, block_k=64,
            interpret=True).astype(jnp.float32)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.mha_reference(
            q, k, v, causal=causal, window=window).astype(jnp.float32)))

    check_grads(f_ker, f_ref, (q, k, v), gtol(dtype))


@pytest.mark.parametrize("mask_kind,S,block,H,KV", [
    pytest.param("db_concat", 48, 32, 2, 2, id="db_concat"),
    pytest.param("two_pass", 48, 32, 2, 2, id="two_pass"),
    # the schedule skips tiles and runs full and partial ones; S = 100
    # straddles a tile; GQA
    ("db_concat", 100, 32, 4, 2), ("db_concat", 96, 64, 4, 1),
    ("two_pass", 100, 32, 4, 1), ("two_pass", 96, 64, 4, 2),
])
def test_flash_attention_db_mask_grads(mask_kind, S, block, H, KV):
    """The DB training masks (App. E.4 concat / two-pass noisy stream)."""
    hd = 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    Sq = 2 * S if mask_kind == "db_concat" else S
    q = jax.random.normal(k1, (1, H, Sq, hd))
    k = jax.random.normal(k2, (1, KV, 2 * S, hd))
    v = jax.random.normal(k3, (1, KV, 2 * S, hd))
    if mask_kind == "db_concat":
        from repro.nn.attention import db_concat_mask
        mask = db_concat_mask(S)(jnp.arange(2 * S), jnp.arange(2 * S))
    else:
        from repro.models.common import two_pass_mask
        mask = two_pass_mask(S)(jnp.arange(S), jnp.arange(2 * S))

    def f_ker(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask_kind=mask_kind,
                                       mask_seq=S, block_q=block,
                                       block_k=block, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(ref.mha_reference_masked(q, k, v, mask) ** 2)

    np.testing.assert_allclose(float(f_ker(q, k, v)), float(f_ref(q, k, v)),
                               rtol=1e-5)
    check_grads(f_ker, f_ref, (q, k, v), 1e-5)


def test_ops_flash_attention_rejects_unsupported():
    """ops.flash_attention must NEVER silently compute wrong attention:
    untagged mask_mods and non-arange concrete positions raise."""
    from repro.kernels import ops
    from repro.nn.attention import causal_mask

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 16))
    with pytest.raises(NotImplementedError):
        ops.flash_attention(q, q, q,
                            mask_mod=lambda qp, kp: kp[None] <= qp[:, None])
    with pytest.raises(NotImplementedError):   # packed-segment positions
        ops.flash_attention(q, q, q, mask_mod=causal_mask,
                            qpos=jnp.array([0, 1, 2, 0] * 8),
                            kpos=jnp.arange(32))
    with pytest.raises(NotImplementedError):   # wrong length
        ops.flash_attention(q, q, q, mask_mod=causal_mask,
                            qpos=jnp.arange(16), kpos=jnp.arange(32))
    out = ops.flash_attention(q, q, q, mask_mod=causal_mask,
                              qpos=jnp.arange(32), kpos=jnp.arange(32))
    assert out.shape == q.shape


def test_flash_attention_no_pallas_autodiff():
    """The VJP must be the hand-written kernels — the backward jaxpr may not
    differentiate through pallas_call (transpose of pallas_call is what
    Mosaic cannot compile). Each pallas_call carries a stable ``name=``
    (which also labels it in profiler traces); the grad jaxpr must hold
    exactly the forward and the two hand-written backward kernels."""
    import re
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 32, 16))

    def f(q):
        return jnp.sum(flash_attention(q, q, q, causal=True, block_q=32,
                                       block_k=32, interpret=True))

    text = str(jax.make_jaxpr(jax.grad(f))(q))
    kernels = set(re.findall(r"name=(flash_attention_\w+)", text))
    assert kernels == {"flash_attention_fwd", "flash_attention_bwd_dq",
                       "flash_attention_bwd_dkv"}, kernels
    g = jax.grad(f)(q)
    assert np.isfinite(np.asarray(g)).all()


def test_flash_attention_grid_is_the_schedule():
    """The kernels run the schedule, not the dense grid: in the gradient's
    jaxpr of a db_concat call, each of the three pallas_calls has the grid
    (B, H, n_live) with n_live the length of ``tile_schedule``'s table."""
    from jax.extend import core as jcore
    from repro.kernels import flash_attention as FA
    B, H, KV, S, hd, blk = 2, 4, 2, 100, 16, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, 2 * S, hd))
    kv = jax.random.normal(jax.random.PRNGKey(1), (B, KV, 2 * S, hd))

    def f(q, kv):
        return jnp.sum(flash_attention(q, kv, kv, mask_kind="db_concat",
                                       mask_seq=S, block_q=blk, block_k=blk,
                                       interpret=True))

    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = eqn.params["grid_mapping"].grid
            for v in eqn.params.values():
                for x in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(x, jcore.ClosedJaxpr):
                        walk(x.jaxpr)
                    elif isinstance(x, jcore.Jaxpr):
                        walk(x)

    walk(jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(q, kv).jaxpr)
    cfg = FA.FlashConfig(mask_kind="db_concat", mask_seq=S, block_q=blk,
                         block_k=blk)
    (q_major, k_major), share = FA.tile_schedule(cfg, 2 * S, 2 * S)
    n = -(-2 * S // blk)
    assert q_major.shape[1] < n * n and share < 0.5
    assert grids == {"flash_attention_fwd": (B, H, q_major.shape[1]),
                     "flash_attention_bwd_dq": (B, H, q_major.shape[1]),
                     "flash_attention_bwd_dkv": (B, H, k_major.shape[1])}


# ---------------------------------------------------------------------------
# fused AdaLN trio
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,d", [(2, 64, 128), (1, 100, 64), (3, 513, 64)])
def test_ln_modulate_grads(B, S, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k1, (B, S, d), dtype)
    sc = (0.1 * jax.random.normal(k2, (B, d))).astype(dtype)
    sh = (0.1 * jax.random.normal(k3, (B, d))).astype(dtype)

    def f_ker(x, sc, sh):
        return jnp.sum(jnp.cos(fused_ln_modulate(
            x, sc, sh, block_rows=64, interpret=True).astype(jnp.float32)))

    def f_ref(x, sc, sh):
        return jnp.sum(jnp.cos(
            ref.ln_modulate_reference(x, sc, sh).astype(jnp.float32)))

    check_grads(f_ker, f_ref, (x, sc, sh), gtol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,d", [(2, 64, 128), (1, 257, 64)])
def test_gate_residual_grads(B, S, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    r = jax.random.normal(k1, (B, S, d), dtype)
    br = jax.random.normal(k2, (B, S, d), dtype)
    g = (0.1 * jax.random.normal(k3, (B, d))).astype(dtype)

    def f_ker(r, br, g):
        return jnp.sum(fused_gate_residual(
            r, br, g, block_rows=64, interpret=True).astype(jnp.float32) ** 2)

    def f_ref(r, br, g):
        return jnp.sum(
            ref.gate_residual_reference(r, br, g).astype(jnp.float32) ** 2)

    check_grads(f_ker, f_ref, (r, br, g), gtol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,d", [(2, 64, 128), (1, 130, 64)])
def test_euler_grads(B, S, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    z = jax.random.normal(k1, (B, S, d), dtype)
    f = jax.random.normal(k2, (B, S, d), dtype)
    sig = jnp.linspace(0.5, 3.0, B)
    sig2 = sig * 0.3

    def f_ker(z, f):
        return jnp.sum(fused_euler(z, f, sig, sig2, 0.5, block_rows=64,
                                   interpret=True).astype(jnp.float32) ** 2)

    def f_ref(z, f):
        return jnp.sum(
            ref.euler_reference(z, f, sig, sig2, 0.5).astype(jnp.float32) ** 2)

    check_grads(f_ker, f_ref, (z, f), gtol(dtype))


def test_euler_sigma_cotangent_is_zero():
    """σ is sampled schedule data — the VJP must not propagate into it."""
    z = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 16))
    sig = jnp.asarray([0.5, 1.5])

    def f(sig):
        return jnp.sum(fused_euler(z, z, sig, sig * 0.5, 0.5, block_rows=32,
                                   interpret=True))

    assert float(jnp.abs(jax.grad(f)(sig)).max()) == 0.0


# ---------------------------------------------------------------------------
# EDM loss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,d", [(2, 64, 128), (1, 300, 64)])
def test_edm_loss_grads(B, S, d):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    f = jax.random.normal(k1, (B, S, d))
    z = jax.random.normal(k2, (B, S, d))
    y = jax.random.normal(k3, (B, S, d))
    sig = jnp.linspace(0.3, 2.0, B)

    def f_ker(f, z, y):
        return edm_loss(f, z, y, sig, 0.5, interpret=True)

    def f_ref(f, z, y):
        return ref.edm_loss_reference(f, z, y, sig, 0.5)

    check_grads(f_ker, f_ref, (f, z, y), 1e-5)


# ---------------------------------------------------------------------------
# end-to-end: make_db_train_step(impl="kernels") vs the reference path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal_mode", ["concat", "two_pass"])
def test_block_loss_grads_kernels_vs_reference(causal_mode):
    from repro.configs.base import DBConfig, ModelConfig
    from repro.core import DiffusionBlocksModel
    from repro.core.training import extract_block_view

    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=17)
    db = DBConfig(num_blocks=2, overlap_gamma=0.1, causal_mode=causal_mode)
    dbm = DiffusionBlocksModel(cfg, db)
    params = dbm.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 17)
    rng = jax.random.PRNGKey(2)
    view = extract_block_view(params, *dbm.ranges[0])
    size = dbm.ranges[0][1]

    def loss(v, impl):
        return dbm.block_loss(v, 0, tokens, rng, impl=impl,
                              unit_range=(0, size))[0]

    lk, gk = jax.value_and_grad(lambda v: loss(v, "kernels"))(view)
    lc, gc = jax.value_and_grad(lambda v: loss(v, "chunked"))(view)
    np.testing.assert_allclose(float(lk), float(lc), rtol=1e-5)
    errs = jax.tree_util.tree_map(rel_err, gk, gc)
    worst = max(jax.tree_util.tree_leaves(errs))
    assert worst <= 1e-4, f"worst grad rel err {worst}"


def test_block_loss_l2_kernels_vs_reference():
    """The loss="l2" branch dispatches kops.edm_loss (kernels) vs
    edm.edm_l2_loss (reference) — values and grads must agree."""
    from repro.configs.base import DBConfig, ModelConfig
    from repro.core import DiffusionBlocksModel
    from repro.core.training import extract_block_view

    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=17)
    db = DBConfig(num_blocks=2, overlap_gamma=0.1, loss="l2")
    dbm = DiffusionBlocksModel(cfg, db)
    params = dbm.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 17)
    rng = jax.random.PRNGKey(2)
    view = extract_block_view(params, *dbm.ranges[0])
    size = dbm.ranges[0][1]

    def loss(v, impl):
        return dbm.block_loss(v, 0, tokens, rng, impl=impl,
                              unit_range=(0, size))[0]

    lk, gk = jax.value_and_grad(lambda v: loss(v, "kernels"))(view)
    lc, gc = jax.value_and_grad(lambda v: loss(v, "chunked"))(view)
    np.testing.assert_allclose(float(lk), float(lc), rtol=1e-5)
    worst = max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(rel_err, gk, gc)))
    assert worst <= 1e-4, f"worst grad rel err {worst}"


def test_kernel_coeffs_match_edm_preconditioning():
    """The kernels re-derive c_skip/c_out locally (kernels stay import-light);
    this pins them to core/edm.preconditioning so a change there cannot
    silently diverge the kernel objective."""
    from repro.core import edm
    from repro.kernels.edm_loss import _coeffs
    from repro.kernels.fused_adaln import _euler_coeffs

    sigma = jnp.asarray([0.05, 0.5, 2.0, 40.0])
    sd = 0.5
    c_skip, c_out, _, _ = edm.preconditioning(sigma, sd)
    ks, ko = _coeffs(sigma, sd)
    np.testing.assert_allclose(np.asarray(ks)[:, 0], np.asarray(c_skip),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ko)[:, 0], np.asarray(c_out),
                               rtol=1e-6)
    sigma_to = sigma * 0.3
    a, b = _euler_coeffs(sigma, sigma_to, sd)
    r = sigma_to / sigma
    np.testing.assert_allclose(np.asarray(a)[:, 0],
                               np.asarray(r + (1 - r) * c_skip), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b)[:, 0],
                               np.asarray((1 - r) * c_out), rtol=1e-6)


def test_db_train_step_kernels_bf16_runs():
    from repro.configs.base import DBConfig, ModelConfig, TrainConfig
    from repro.core import DiffusionBlocksModel
    from repro.core.training import make_db_train_step

    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=17)
    dbm = DiffusionBlocksModel(cfg, DBConfig(num_blocks=2, overlap_gamma=0.1))
    tcfg = TrainConfig(steps=2, batch_size=2, seq_len=16, log_every=0)
    params = dbm.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 17)
    io, st = make_db_train_step(dbm, 0, tcfg, impl="kernels",
                                precision="bf16")
    opt = io(params)
    p2, opt, loss, m = st(params, opt, tokens, jax.random.PRNGKey(2))
    assert np.isfinite(float(loss))
    # masters stay fp32 — mixed precision must not downcast the stored params
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(p2)
               if jnp.issubdtype(x.dtype, jnp.floating))
