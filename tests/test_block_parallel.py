"""Block-parallel engine (repro.parallel): stacked-view round-trips, exact
agreement with the sequential per-block trainer, periphery sync policies,
the round-robin fallback schedule, and per-block optimizer checkpoints.

The multi-device tests need a pod per block; CI provides them via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (they skip on a plain
1-device run — the fallback-path tests still cover the shared math there)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DBConfig
from repro.configs.base import ModelConfig, TrainConfig
from repro.core import DiffusionBlocksModel
from repro.core.training import extract_block_view, make_db_train_step
from repro.data import arithmetic_stream
from repro.parallel import (BlockParallelTrainer, merge_params,
                            split_periphery, stack_block_views)

TINY8 = ModelConfig(name="tiny8", family="dense", n_layers=8, d_model=64,
                    n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=64)
B = 4

needs_pods = pytest.mark.skipif(
    jax.device_count() < B,
    reason=f"needs >= {B} devices "
           f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def dbm():
    return DiffusionBlocksModel(TINY8, DBConfig(num_blocks=B,
                                                overlap_gamma=0.05))


@pytest.fixture(scope="module")
def params(dbm):
    return dbm.init(jax.random.PRNGKey(0))


def tcfg(steps=8, **kw):
    kw.setdefault("lr", 2e-3)
    kw.setdefault("warmup_steps", 2)
    kw.setdefault("log_every", 0)
    return TrainConfig(steps=steps, **kw)


def data_it(seed=0, batch=8, seq=16):
    s = seed
    while True:
        s += 1
        yield jnp.asarray(arithmetic_stream(batch, seq, 64, s))


def tree_equal(a, b, **tol):
    for (pa, x), (_, y) in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                               jax.tree_util.tree_flatten_with_path(b)[0]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   err_msg=str(pa), **tol)


# ---------------------------------------------------------------------------
# (a) stacked views round-trip; non-owned slices stay bit-exact
# ---------------------------------------------------------------------------
def test_stacked_view_roundtrip_bit_exact(dbm, params):
    stacks, periph = stack_block_views(params, dbm.ranges), \
        split_periphery(params)[1]
    back = merge_params(params, stacks, periph, dbm.ranges)
    tree_equal(back, params, atol=0, rtol=0)


def test_writeback_preserves_non_owned_slices(dbm, params):
    """Perturb ONE block's stacked slice; every other block's units must
    round-trip bit-exactly through extract → write_back."""
    stacks, periph = stack_block_views(params, dbm.ranges), \
        split_periphery(params)[1]
    victim = 2
    stacks2 = jax.tree_util.tree_map(
        lambda x: x.at[victim].add(1.0), stacks)
    back = merge_params(params, stacks2, periph, dbm.ranges)
    for b, (start, size) in enumerate(dbm.ranges):
        got = extract_block_view(back, start, size)
        ref = extract_block_view(params, start, size)
        for k in ("layers",):
            if b == victim:
                tree_equal(got[k],
                           jax.tree_util.tree_map(lambda x: x + 1.0, ref[k]),
                           atol=0, rtol=0)
            else:
                tree_equal(got[k], ref[k], atol=0, rtol=0)


def test_unequal_block_sizes_rejected():
    cfg = ModelConfig(name="tiny6", family="dense", n_layers=6, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=64)
    dbm6 = DiffusionBlocksModel(cfg, DBConfig(num_blocks=4))   # 6 units / 4
    with pytest.raises(ValueError, match="equal-sized"):
        BlockParallelTrainer(dbm6, tcfg())


# ---------------------------------------------------------------------------
# (b) parallel step/run ≡ sequential per-block training
# ---------------------------------------------------------------------------
@needs_pods
def test_parallel_step_matches_sequential_per_block(dbm, params):
    """One shard_map step (data=1 for bit-reproducible draws) must reproduce
    ``make_db_train_step``'s loss AND stack update for every block."""
    cfg = tcfg()
    tokens = jnp.asarray(arithmetic_stream(8, 16, 64, 1))
    key = jax.random.PRNGKey(7)
    tr = BlockParallelTrainer(dbm, cfg, devices=jax.devices()[:B])
    assert tr.mode == "shard_map" and dict(tr.mesh.shape)["data"] == 1
    state, losses, _ = tr.step(tr.init_state(params), tokens,
                               jnp.stack([key] * B))
    full = tr.full_params(state)
    for b in range(B):
        init_opt, step = make_db_train_step(dbm, b, cfg)
        p_ref, _, loss_ref, _ = step(params, init_opt(params), tokens, key,
                                     None)
        np.testing.assert_allclose(float(losses[b]), float(loss_ref),
                                   rtol=1e-5)
        start, size = dbm.ranges[b]
        tree_equal(extract_block_view(full, start, size)["layers"],
                   extract_block_view(p_ref, start, size)["layers"],
                   atol=1e-6, rtol=1e-6)


@needs_pods
def test_shard_map_trajectory_matches_round_robin(dbm):
    """The device-parallel engine and the round-robin fallback are the same
    algorithm: identical rng stream → per-block loss trajectories agree."""
    cfg = tcfg(steps=3 * B)
    kw = dict(rng=jax.random.PRNGKey(3), log=lambda *_: None)
    tr_p = BlockParallelTrainer(dbm, cfg, devices=jax.devices()[:B])
    tr_f = BlockParallelTrainer(dbm, cfg, devices=jax.devices()[:1])
    assert tr_p.mode == "shard_map" and tr_f.mode == "round_robin"
    _, hist_p = tr_p.train(data_it(), **kw)
    _, hist_f = tr_f.train(data_it(), **kw)
    assert len(hist_p) == len(hist_f) == 3 * B
    for (it_p, b_p, l_p), (it_f, b_f, l_f) in zip(hist_p, hist_f):
        assert (it_p, b_p) == (it_f, b_f)
        np.testing.assert_allclose(l_p, l_f, rtol=1e-4)


# ---------------------------------------------------------------------------
# (c) graceful degradation when devices < blocks
# ---------------------------------------------------------------------------
def test_fallback_schedule_when_devices_insufficient(dbm):
    tr = BlockParallelTrainer(dbm, tcfg(), devices=jax.devices()[:1])
    assert tr.mode == "round_robin" and tr.mesh is None
    _, hist = tr.train(data_it(), jax.random.PRNGKey(0), log=lambda *_: None)
    assert len(hist) == 8                       # ceil(steps/B) * B entries
    assert [b for _, b, _ in hist] == [0, 1, 2, 3, 0, 1, 2, 3]
    assert all(np.isfinite(l) for _, _, l in hist)


def test_train_db_parallel_entrypoint(dbm):
    from repro.core import train_db
    _, hist = train_db(dbm, tcfg(steps=B), data_it(), jax.random.PRNGKey(0),
                       log=lambda *_: None, parallel="blocks")
    assert len(hist) == B
    with pytest.raises(ValueError, match="parallel"):
        train_db(dbm, tcfg(steps=B), data_it(), jax.random.PRNGKey(0),
                 parallel="banana")


# ---------------------------------------------------------------------------
# periphery sync policies
# ---------------------------------------------------------------------------
def test_freeze_after_warmup_stops_periphery(dbm, params):
    tr = BlockParallelTrainer(dbm, tcfg(), periphery="freeze-after-warmup",
                              freeze_steps=1, devices=jax.devices()[:1])
    state = tr.init_state(params)
    it = data_it()
    key = jax.random.PRNGKey(1)
    s1, _, _ = tr.step(state, next(it), jax.random.split(key, B))
    # warmup step: periphery moved
    moved = any(not np.allclose(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree_util.tree_leaves(s1.periph),
                                jax.tree_util.tree_leaves(state.periph)))
    assert moved
    s2, _, _ = tr.step(s1, next(it), jax.random.split(key, B))
    tree_equal(s2.periph, s1.periph, atol=0, rtol=0)   # frozen
    # ...but blocks keep training
    assert any(not np.allclose(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(s2.stacks),
                               jax.tree_util.tree_leaves(s1.stacks)))


def test_owner_broadcast_uses_owner_gradients_only(dbm, params):
    """Under owner-broadcast the periphery update must be exactly the AdamW
    step on the OWNER block's (clipped) periphery grads.

    The reference grads come from a separately compiled program, so they
    differ from the engine's in the last fp32 bits (reduction order). With
    AdamW's default eps=1e-8 the first step is u = lr·g/(|g|+eps): for the
    near-zero periphery grads (|g| ~ 1e-9) that is a sign function whose
    slope lr/eps = 2e5 turns 1e-9 of reduction noise into ~1e-5 of update.
    eps=1e-3 bounds the slope at lr/eps = 2, so 1e-8 of grad noise stays
    ~50x under the 1e-6 tolerance, while mixing in any non-owner gradient
    (O(1e-3) per element) still moves the update far past it. Independence
    from the other blocks is also checked exactly: re-drawing every
    non-owner block's σ/ε leaves the periphery bit-identical."""
    from repro.optim import apply_updates, clip_by_global_norm
    from repro.parallel.engine import _split_optimizer
    cfg = tcfg(eps=1e-3)
    tokens = jnp.asarray(arithmetic_stream(8, 16, 64, 1))
    key = jax.random.PRNGKey(9)
    tr = BlockParallelTrainer(dbm, cfg, periphery="owner-broadcast",
                              devices=jax.devices()[:1])
    state = tr.init_state(params)
    s1, _, _ = tr.step(state, tokens, jnp.stack([key] * B))

    owner = B - 1
    others = jax.random.split(jax.random.PRNGKey(10), B)
    s2, _, _ = tr.step(state, tokens, others.at[owner].set(key))
    for x, y in zip(jax.tree_util.tree_leaves(s2.periph),
                    jax.tree_util.tree_leaves(s1.periph)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    start, size = dbm.ranges[owner]
    view = extract_block_view(params, start, size)
    g = jax.grad(lambda v: dbm.block_loss(
        v, owner, tokens, key, unit_range=(0, size))[0])(view)
    g, _ = clip_by_global_norm(g, cfg.grad_clip)
    g_per = {k: v for k, v in g.items() if k not in ("layers", "units")}
    opt_init, opt_update = _split_optimizer(cfg)
    popt = opt_init(split_periphery(params)[1])
    upd, _, _ = opt_update(g_per, popt, split_periphery(params)[1])
    ref = apply_updates(split_periphery(params)[1], upd)
    tree_equal(s1.periph, ref, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# per-block checkpoints from the mesh
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_per_block_opt(dbm, params, tmp_path):
    tr = BlockParallelTrainer(dbm, tcfg(), devices=jax.devices()[:1])
    state = tr.init_state(params)
    state, _, _ = tr.step(state, jnp.asarray(arithmetic_stream(8, 16, 64, 1)),
                          jax.random.split(jax.random.PRNGKey(2), B))
    tr.save_checkpoint(state, str(tmp_path), step=B)
    for b in range(B):
        assert (tmp_path / f"block_{b:02d}.npz").exists()
        assert (tmp_path / f"block_{b:02d}.opt.npz").exists()
    assert (tmp_path / "periphery.opt.npz").exists()
    restored = tr.restore(dbm.init(jax.random.PRNGKey(99)), str(tmp_path))
    tree_equal(restored.stacks, state.stacks, atol=1e-6, rtol=1e-6)
    tree_equal(restored.periph, state.periph, atol=1e-6, rtol=1e-6)
    tree_equal(restored.stack_opt, state.stack_opt, atol=1e-6, rtol=1e-6)
    tree_equal(restored.periph_opt, state.periph_opt, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# periphery lr compensation (1-vs-B update-count gap)
# ---------------------------------------------------------------------------
def test_periphery_lr_scale_compensates_update_cadence():
    """With ``lr_scale=B`` the periphery optimizer's first update must be
    exactly B * sched(B) / sched(1) times the unscaled one: rate scaled by B
    AND the warmup/cosine schedule evaluated at the equivalent block-update
    count."""
    from repro.optim.schedules import warmup_cosine
    from repro.parallel.engine import _split_optimizer
    cfg = tcfg(steps=32)
    base_init, base_upd = _split_optimizer(cfg)
    comp_init, comp_upd = _split_optimizer(cfg, lr_scale=float(B))
    p = {"w": jnp.ones((4,))}
    g = {"w": jnp.full((4,), 0.5)}
    u_b, _, _ = base_upd(g, base_init(p), p)
    u_c, _, _ = comp_upd(g, comp_init(p), p)
    sched = warmup_cosine(cfg.lr, cfg.warmup_steps, cfg.steps)
    ratio = float(B * sched(jnp.asarray(1.0 * B)) / sched(jnp.asarray(1.0)))
    np.testing.assert_allclose(np.asarray(u_c["w"]) / np.asarray(u_b["w"]),
                               ratio, rtol=1e-5)


@pytest.mark.slow
def test_periphery_lr_compensation_convergence_parity(dbm):
    """Same data/rng, same per-block-update budget: the compensated engine's
    final losses must land strictly closer to the sequential trainer's than
    the uncompensated engine's (whose periphery moves B× too slowly), and
    within an absolute band of the sequential tail."""
    from repro.core import train_db
    cfg = tcfg(steps=6 * B)
    kw = dict(log=lambda *_: None)
    _, h_seq = train_db(dbm, cfg, data_it(), jax.random.PRNGKey(5), **kw)
    _, h_comp = train_db(dbm, cfg, data_it(), jax.random.PRNGKey(5),
                         parallel="blocks", periphery_lr_scale="auto", **kw)
    _, h_unc = train_db(dbm, cfg, data_it(), jax.random.PRNGKey(5),
                        parallel="blocks", **kw)
    tail = lambda h: float(np.mean([l for _, _, l in h[-2 * B:]]))  # noqa: E731
    t_seq, t_comp, t_unc = tail(h_seq), tail(h_comp), tail(h_unc)
    assert np.isfinite(t_comp)
    assert abs(t_comp - t_seq) < abs(t_unc - t_seq)
    assert abs(t_comp - t_seq) < 0.9
