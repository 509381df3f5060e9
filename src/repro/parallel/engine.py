"""Block-parallel DiffusionBlocks training across devices.

The paper's §3 independence result says block b's objective never reads
another block's gradients — the only shared state is the periphery
(embeddings / readout / final norm / σ-conditioning). This engine turns that
structural fact into wall-clock parallelism: a 2-D (``pod`` × ``data``) mesh
gives every block its own pod group, and ONE jitted ``shard_map`` call per
batch advances all B blocks — per-block score-matching losses, per-block
AdamW moments, zero cross-pod optimizer collectives.

Periphery sync policies (``periphery=``):

  ``replicate+psum-mean``   every block computes periphery gradients on the
        full batch; they are psum-averaged across pods each step and one
        AdamW update is applied identically everywhere (data-parallel
        semantics for the shared params; the replication invariant is exact).
        Highest fidelity, one psum of periphery-sized grads per step.
  ``owner-broadcast``       only the OWNER block (B-1, the lowest-noise
        block — the same block whose checkpoint supplies the periphery in
        ``repro.checkpoint.load_blocks``) contributes periphery gradients;
        the psum then just broadcasts them. Cheaper semantics when the
        low-noise block dominates readout quality; other blocks' periphery
        preferences are ignored.
  ``freeze-after-warmup``   psum-mean for the first ``freeze_steps`` updates,
        then the periphery stops moving entirely — blocks become FULLY
        independent (the psum still executes but its result is discarded by
        a select, keeping one compiled program). Zero effective cross-block
        coupling after warmup; final loss depends on the warmup being long
        enough to settle the embedding geometry.

Degradation: when the host has fewer devices than blocks (or the block sizes
are unequal) the same math runs as a round-robin ``lax.scan`` over blocks on
the default device — one block's activations in memory at a time, identical
per-block losses — so CPU CI (``--xla_force_host_platform_device_count=8``)
and a laptop both run the one code path they can.
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import precision as precision_mod
from repro import tracing
from repro.configs.base import TrainConfig
from repro.core import partition as P
from repro.core.blocks import DiffusionBlocksModel
from repro.core.training import GuardConfig
from repro.optim import adamw, apply_updates, clip_by_global_norm, global_norm
from repro.optim.schedules import warmup_cosine
from repro.parallel.state import (BlockParallelState, split_periphery,
                                  stack_block_views, uniform_block_size)
from repro.sharding import rules

PERIPHERY_POLICIES = ("replicate+psum-mean", "owner-broadcast",
                      "freeze-after-warmup")
_POLICY_ALIASES = {"mean": "replicate+psum-mean", "psum-mean":
                   "replicate+psum-mean", "owner": "owner-broadcast",
                   "broadcast": "owner-broadcast", "freeze":
                   "freeze-after-warmup"}


def _split_optimizer(tcfg: TrainConfig, lr_scale: float = 1.0):
    """Same AdamW/schedule as ``make_db_train_step``'s, but with clipping
    hoisted out: the engine clips each block's FULL view grads (stack +
    periphery, matching the sequential per-block step) before the periphery
    reduction splits them across two optimizers.

    ``lr_scale`` compensates the periphery's 1-vs-B update-count gap: the
    sequential trainer applies one periphery AdamW update per BLOCK update
    (B per batch-equivalent), the parallel engine one per BATCH. With
    ``lr_scale = B`` the periphery rate is scaled by B and the warmup/cosine
    schedule is evaluated at the equivalent block-update count, so the
    periphery trajectory tracks the sequential cadence to first order."""
    base = warmup_cosine(tcfg.lr, tcfg.warmup_steps, tcfg.steps)
    if lr_scale == 1.0:
        lr = base
    else:
        def lr(step):
            return lr_scale * base(step.astype(jnp.float32) * lr_scale)
    return adamw(lr, tcfg.b1, tcfg.b2, tcfg.eps,
                 weight_decay=tcfg.weight_decay, grad_clip=None)


class BlockParallelTrainer:
    """Trains all B blocks concurrently; see module docstring.

    ``mode`` is ``"shard_map"`` when every block got a pod group, else
    ``"round_robin"``. ``devices`` restricts the mesh (e.g. ``devices=
    jax.devices()[:B]`` forces data=1 for bit-reproducible comparisons).
    """

    def __init__(self, dbm: DiffusionBlocksModel, tcfg: TrainConfig,
                 periphery: str = "replicate+psum-mean",
                 freeze_steps: Optional[int] = None, impl: str = "auto",
                 devices=None, jit: bool = True, precision=None,
                 periphery_lr_scale=None, guard: Optional[GuardConfig] = None):
        self.dbm, self.tcfg, self.impl = dbm, tcfg, impl
        self.precision = precision_mod.get_policy(precision)
        self.policy = _POLICY_ALIASES.get(periphery, periphery)
        if self.policy not in PERIPHERY_POLICIES:
            raise ValueError(f"unknown periphery policy {periphery!r}; "
                             f"one of {PERIPHERY_POLICIES}")
        self.B = dbm.num_blocks
        self.u = uniform_block_size(dbm.ranges)
        self.guard = GuardConfig() if guard is None else guard
        self.anomaly_streak = np.zeros(self.B, np.int64)
        self.anomalies = np.zeros(self.B, np.int64)
        self.last_ok = np.ones(self.B, bool)
        self.freeze_steps = (tcfg.warmup_steps if freeze_steps is None
                             else freeze_steps)
        self.mesh = rules.block_parallel_mesh(self.B, devices)
        self.mode = "shard_map" if self.mesh is not None else "round_robin"
        self.qranges = jnp.asarray(P.block_qranges(dbm.db))        # (B, 2)
        self.block_ids = jnp.arange(self.B)
        if periphery_lr_scale in (None, "none"):
            self.periphery_lr_scale = 1.0
        elif periphery_lr_scale == "auto":
            self.periphery_lr_scale = float(self.B)
        else:
            self.periphery_lr_scale = float(periphery_lr_scale)
        self._opt_init, self._opt_update = _split_optimizer(tcfg)
        self._popt_init, self._popt_update = _split_optimizer(
            tcfg, self.periphery_lr_scale)
        self._step_fn = self._build_step(jit)
        if self.mesh is not None:
            sp = NamedSharding(self.mesh, rules.block_state_specs()["stacked"])
            self.qranges = jax.device_put(self.qranges, sp)
            self.block_ids = jax.device_put(self.block_ids, sp)
        self._set_ewma(jnp.full((self.B,), -1.0, jnp.float32))

    def _set_ewma(self, ewma):
        """The loss EWMA enters every step placed as the step returns it, so
        the second batch does not compile the step again."""
        if self.mesh is not None:
            ewma = jax.device_put(ewma, NamedSharding(
                self.mesh, rules.block_state_specs()["stacked"]))
        self.guard_ewma = ewma

    # ------------------------------------------------------------------
    def _build_step(self, jit: bool):
        dbm, tcfg, u, B = self.dbm, self.tcfg, self.u, self.B
        policy, impl, freeze_steps = self.policy, self.impl, self.freeze_steps
        pol = self.precision
        guard = self.guard
        opt_update = self._opt_update
        popt_update = self._popt_update
        pod_ax = rules.BLOCK_AXIS if self.mode == "shard_map" else None
        data_size = self.mesh.shape["data"] if self.mesh is not None else 1
        data_ax = "data" if (self.mode == "shard_map" and data_size > 1) \
            else None

        def block_grads(view, tokens, rng, q_lo, q_hi, loss_mult):
            if data_ax is not None:
                # each data shard must draw its OWN σ/ε for its batch slice
                rng = jax.random.fold_in(rng, jax.lax.axis_index(data_ax))

            def loss_fn(v):
                with tracing.scope(tracing.BLOCK_VIEW):
                    vc = precision_mod.cast_params_for_compute(
                        pol, v, dbm.cfg.family)
                loss, metrics = dbm.block_loss(vc, 0, tokens, rng, impl=impl,
                                               unit_range=(0, u),
                                               sigma_qrange=(q_lo, q_hi),
                                               precision=pol)
                # the grad_nan injection point: NaN loss_mult → NaN grads;
                # the multiply by the usual 1.0 is bit-exact
                return loss * loss_mult, metrics

            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(view)
            if data_ax is not None:
                grads = jax.lax.pmean(grads, data_ax)
                loss = jax.lax.pmean(loss, data_ax)
            with tracing.scope(tracing.OPTIMIZER):
                if tcfg.grad_clip is not None:
                    grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
                else:
                    gnorm = global_norm(grads)
            return loss, grads, gnorm

        def local_update(stacks, stack_opt, periph, periph_opt, tokens,
                         rngs, qranges, block_ids, loss_mult, active, ewma,
                         upd_periph):
            """Advance the (locally held) blocks; scan keeps only ONE block's
            activations live at a time — under shard_map each pod holds one
            block (scan length 1); in round-robin mode the scan IS the
            schedule. Per-block ANOMALY GUARD: a non-finite or spiking loss
            (or ``active=0``, a dead pod) skips that block's stack update and
            masks its periphery contribution out of the psum; the clean path
            is bit-identical to the unguarded engine (selects of the same
            values, scale exactly 1.0)."""

            def body(acc, xs):
                stack_b, opt_b, rng_b, qr_b, bid, mult_b, act_b, ewma_b = xs
                view = {**periph, **stack_b}
                loss, grads, gnorm = block_grads(view, tokens, rng_b,
                                                 qr_b[0], qr_b[1], mult_b)
                with tracing.scope(tracing.GUARD):
                    ok, ewma_b = guard.classify(loss, gnorm, ewma_b,
                                                act_b > 0)
                g_stack = {k: grads[k] for k in stack_b}
                g_per = {k: grads[k] for k in periph}
                with tracing.scope(tracing.PSUM):
                    if policy == "owner-broadcast":
                        w = (bid == B - 1).astype(jnp.float32)
                    else:
                        w = jnp.float32(1.0 / B)
                    w = jnp.where(ok, w, 0.0)
                    acc_g, acc_n, acc_w = acc
                    acc_g = jax.tree_util.tree_map(
                        lambda a, g: a + w * jnp.where(
                            ok, g.astype(jnp.float32), 0.0), acc_g, g_per)
                    acc_n = acc_n + ok.astype(jnp.int32)
                    acc_w = acc_w + w
                with tracing.scope(tracing.OPTIMIZER):
                    updates, opt_b2, _ = opt_update(g_stack, opt_b, stack_b)
                    stack_b2 = apply_updates(stack_b, updates)
                with tracing.scope(tracing.GUARD):
                    sel = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
                    stack_b = jax.tree_util.tree_map(sel, stack_b2, stack_b)
                    opt_b = jax.tree_util.tree_map(sel, opt_b2, opt_b)
                return (acc_g, acc_n, acc_w), (stack_b, opt_b, loss, gnorm,
                                               ok, ewma_b)

            acc0 = (jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), periph),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
            acc, (stacks, stack_opt, losses, gnorms, oks, ewma) = \
                jax.lax.scan(body, acc0, (stacks, stack_opt, rngs, qranges,
                                          block_ids, loss_mult, active, ewma))
            acc_g, acc_n, acc_w = acc
            with tracing.scope(tracing.PSUM):
                if pod_ax is not None:
                    acc_g = jax.lax.psum(acc_g, pod_ax)
                    acc_n = jax.lax.psum(acc_n, pod_ax)
                    acc_w = jax.lax.psum(acc_w, pod_ax)
                # renormalize the periphery mean over the SURVIVING blocks.
                # In the owner policy acc_g already carries exactly the
                # owner's grads (w ∈ {0,1}), so the scale stays 1; in the
                # mean policies B/n_ok re-weights the (1/B)Σ_ok sum to a
                # true mean — exactly 1.0 when every block is clean
                # (bit-parity with the old path).
                if policy == "owner-broadcast":
                    scale = jnp.float32(1.0)
                    per_ok = acc_w > 0
                else:
                    scale = B / jnp.maximum(acc_n.astype(jnp.float32), 1.0)
                    per_ok = acc_n > 0
                g_per = jax.tree_util.tree_map(lambda a: a * scale, acc_g)
            with tracing.scope(tracing.OPTIMIZER):
                updates, new_popt, _ = popt_update(g_per, periph_opt, periph)
                new_periph = apply_updates(periph, updates)
            with tracing.scope(tracing.GUARD):
                do_per = per_ok & upd_periph
                sel_p = lambda new, old: jnp.where(do_per, new, old)  # noqa: E731
                new_periph = jax.tree_util.tree_map(sel_p, new_periph, periph)
                new_popt = jax.tree_util.tree_map(sel_p, new_popt, periph_opt)
                if policy == "freeze-after-warmup":
                    frozen = periph_opt.step >= freeze_steps
                    keep = lambda old, new: jnp.where(frozen, old, new)  # noqa: E731
                    new_periph = jax.tree_util.tree_map(keep, periph,
                                                        new_periph)
                    new_popt = jax.tree_util.tree_map(keep, periph_opt,
                                                      new_popt)
            return (stacks, stack_opt, new_periph, new_popt, losses, gnorms,
                    oks, ewma)

        fn = local_update
        if self.mode == "shard_map":
            specs = rules.block_state_specs()
            sp, rp, tk = specs["stacked"], specs["replicated"], specs["tokens"]
            fn = jax.shard_map(local_update, mesh=self.mesh,
                               in_specs=(sp, sp, rp, rp, tk, sp, sp, sp, sp,
                                         sp, sp, rp),
                               out_specs=(sp, sp, rp, rp, sp, sp, sp, sp),
                               check_vma=False)
        return jax.jit(fn) if jit else fn

    # ------------------------------------------------------------------
    def init_state(self, params) -> BlockParallelState:
        stacks = stack_block_views(params, self.dbm.ranges)
        _, periph = split_periphery(params)
        stack_opt = jax.vmap(self._opt_init)(stacks)
        periph_opt = self._popt_init(periph)
        if self.mesh is not None:
            specs = rules.block_state_specs()
            sp = NamedSharding(self.mesh, specs["stacked"])
            rp = NamedSharding(self.mesh, specs["replicated"])
            stacks = jax.device_put(stacks, sp)
            stack_opt = jax.device_put(stack_opt, sp)
            periph = jax.device_put(periph, rp)
            periph_opt = jax.device_put(periph_opt, rp)
        return BlockParallelState(stacks, periph, stack_opt, periph_opt)

    def step(self, state: BlockParallelState, tokens, rngs, loss_mult=None,
             active=None, update_periphery: bool = True):
        """One batch → one update of EVERY block. ``rngs``: (B, 2) per-block
        PRNG keys. Returns (state', per-block losses (B,), grad norms (B,)).

        ``loss_mult`` (B,) scales each block's loss inside the grad (the
        ``grad_nan`` injection point; default all-ones is bit-neutral).
        ``active`` (B,) masks blocks out entirely (dead pods / orphan-only
        degraded passes): an inactive block gets no stack update and no
        periphery contribution. ``update_periphery=False`` freezes the
        periphery for this call (used by the supervisor's orphan round-robin
        passes so the mesh remains the single periphery writer).

        Guard outcomes land on the trainer: ``last_ok`` (B,) bool,
        cumulative ``anomalies``, consecutive ``anomaly_streak`` (only
        blocks that actually ran are counted), and the per-block loss EWMA
        ``guard_ewma`` advances only on clean steps."""
        B = self.B
        with tracing.span(tracing.PLACE):
            loss_mult = (jnp.ones((B,), jnp.float32) if loss_mult is None
                         else jnp.asarray(loss_mult, jnp.float32))
            active = (jnp.ones((B,), jnp.float32) if active is None
                      else jnp.asarray(active, jnp.float32))
            if self.mesh is not None:
                specs = rules.block_state_specs()
                tokens = jax.device_put(
                    tokens, NamedSharding(self.mesh, specs["tokens"]))
                sp = NamedSharding(self.mesh, specs["stacked"])
                loss_mult = jax.device_put(loss_mult, sp)
                active = jax.device_put(active, sp)
        with tracing.span(tracing.DISPATCH):
            (stacks, stack_opt, periph, periph_opt, losses, gnorms, oks,
             ewma) = self._step_fn(
                state.stacks, state.stack_opt, state.periph,
                state.periph_opt, tokens, rngs, self.qranges,
                self.block_ids, loss_mult, active, self.guard_ewma,
                jnp.asarray(bool(update_periphery)))
        with tracing.span(tracing.GUARD_SYNC):
            self.guard_ewma = ewma
            oks_np = np.asarray(oks).astype(bool)
            ran = np.asarray(active) > 0
            bad = ran & ~oks_np
            self.last_ok = oks_np | ~ran
            self.anomalies += bad
            self.anomaly_streak = np.where(
                bad, self.anomaly_streak + 1,
                np.where(ran, 0, self.anomaly_streak))
        return (BlockParallelState(stacks, periph, stack_opt, periph_opt),
                losses, gnorms)

    # ------------------------------------------------------------------
    def guard_state(self) -> dict:
        """JSON-serializable guard state (manifest payload)."""
        return {"ewma": [float(x) for x in np.asarray(self.guard_ewma)],
                "streak": [int(x) for x in self.anomaly_streak],
                "anomalies": [int(x) for x in self.anomalies]}

    def set_guard_state(self, gs: Optional[dict]) -> None:
        if not gs:
            return
        self._set_ewma(jnp.asarray(np.asarray(gs["ewma"], np.float32)))
        self.anomaly_streak = np.asarray(gs["streak"], np.int64)
        self.anomalies = np.asarray(gs["anomalies"], np.int64)

    def block_trees(self, state: BlockParallelState, b: int):
        """(stack_view, opt_view) for block ``b`` — host-side slices of the
        stacked state (checkpoint payloads, rewind templates)."""
        stack = jax.device_get(jax.tree_util.tree_map(
            lambda x: x[b], state.stacks))
        opt = jax.device_get(jax.tree_util.tree_map(
            lambda x: x[b], state.stack_opt))
        return stack, opt

    def write_block(self, state: BlockParallelState, b: int, stack_view,
                    opt_view) -> BlockParallelState:
        """Overwrite ONE block's stacked slice + optimizer moments (rewind /
        pod re-adoption) — every other block's state is untouched."""
        stacks = jax.tree_util.tree_map(
            lambda whole, blk: whole.at[b].set(
                jnp.asarray(blk, whole.dtype)), state.stacks, stack_view)
        stack_opt = jax.tree_util.tree_map(
            lambda whole, blk: whole.at[b].set(
                jnp.asarray(blk, whole.dtype)), state.stack_opt, opt_view)
        self.anomaly_streak[b] = 0
        self.guard_ewma = self.guard_ewma.at[b].set(-1.0)
        return BlockParallelState(stacks, state.periph, stack_opt,
                                  state.periph_opt)

    # ------------------------------------------------------------------
    def train(self, data_iter, rng, params=None, log=print,
              ckpt_dir: Optional[str] = None):
        """Counterpart of ``train_db``: ``tcfg.steps`` is the TOTAL budget of
        per-block updates, so the engine runs ceil(steps / B) batches and the
        returned history carries one (it, block, loss) entry per block-update
        — directly comparable to the sequential trajectory. A batch advances
        ALL blocks, so a budget not divisible by B executes up to B-1 extra
        updates in the final batch; the history is truncated to ``steps``
        entries either way."""
        tcfg = self.tcfg
        rng, r0 = jax.random.split(rng)
        if params is None:
            params = self.dbm.init(r0)
        state = self.init_state(params)
        history, it = [], 0
        batches = math.ceil(tcfg.steps / self.B)
        for bt in range(batches):
            tokens = next(data_iter)
            rng, rs = jax.random.split(rng)
            state, losses, gnorms = self.step(state, tokens,
                                              jax.random.split(rs, self.B))
            losses = np.asarray(losses)
            for b in range(self.B):
                if it < tcfg.steps:
                    history.append((it, b, float(losses[b])))
                it += 1
            if tcfg.log_every and bt % tcfg.log_every == 0:
                log(f"[db-par/{self.mode}/{self.policy}] batch={bt} "
                    f"loss={losses.mean():.4f} "
                    f"gn={float(np.asarray(gnorms).mean()):.2f}")
        if ckpt_dir:
            self.save_checkpoint(state, ckpt_dir, step=it)
        return self.full_params(state), history

    # ------------------------------------------------------------------
    def full_params(self, state: BlockParallelState) -> dict:
        """Assemble the full params tree from the mesh-resident state. The
        engine enforces contiguous equal-sized blocks, so flattening each
        (B, u, ...) stacked leaf back to (B·u, ...) IS the full unit stack
        (``merge_params`` is the general-template form used by the tests)."""
        stacks = jax.device_get(state.stacks)
        periph = jax.device_get(state.periph)
        return {**{k: jax.tree_util.tree_map(
            lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), v)
            for k, v in stacks.items()}, **periph}

    def save_checkpoint(self, state: BlockParallelState, ckpt_dir: str,
                        step: int = 0):
        """Per-block params + per-block optimizer moments + the periphery
        optimizer — each pod's block is recoverable independently."""
        from repro.checkpoint import (save_block, save_block_opt, save_pytree)
        params = self.full_params(state)
        for b, (start, size) in enumerate(self.dbm.ranges):
            save_block(ckpt_dir, params, b, start, size, step)
            opt_b = jax.device_get(jax.tree_util.tree_map(
                lambda x: x[b], state.stack_opt))
            save_block_opt(ckpt_dir, b, opt_b, step)
        save_pytree(os.path.join(ckpt_dir, "periphery.opt.npz"),
                    jax.device_get(state.periph_opt), {"step": step})

    def restore(self, params_template, ckpt_dir: str) -> BlockParallelState:
        """Rebuild mesh-resident state from per-block checkpoints; blocks or
        optimizer files that are missing keep their fresh initialization."""
        from repro.checkpoint import load_block_opt, load_blocks, load_pytree
        params = load_blocks(ckpt_dir, params_template, self.dbm.ranges)
        state = self.init_state(params)
        opt_slices = []
        for b in range(self.B):
            tmpl = jax.tree_util.tree_map(lambda x: x[b], state.stack_opt)
            loaded = load_block_opt(ckpt_dir, b, tmpl)
            opt_slices.append(tmpl if loaded is None else loaded)
        stack_opt = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *opt_slices)
        periph_opt = state.periph_opt
        ppath = os.path.join(ckpt_dir, "periphery.opt.npz")
        if os.path.exists(ppath):
            periph_opt = load_pytree(ppath, periph_opt)
        if self.mesh is not None:
            specs = rules.block_state_specs()
            stack_opt = jax.device_put(
                stack_opt, NamedSharding(self.mesh, specs["stacked"]))
            periph_opt = jax.device_put(
                periph_opt, NamedSharding(self.mesh, specs["replicated"]))
        return BlockParallelState(state.stacks, state.periph, stack_opt,
                                  periph_opt)


def train_db_parallel(dbm: DiffusionBlocksModel, tcfg: TrainConfig, data_iter,
                      rng, params=None, log=print,
                      periphery: str = "replicate+psum-mean",
                      devices=None, ckpt_dir: Optional[str] = None,
                      impl: str = "auto", precision=None,
                      periphery_lr_scale=None):
    """Functional wrapper mirroring ``train_db``'s signature.
    ``periphery_lr_scale``: None (off), "auto" (scale by B), or a float —
    compensates the periphery's 1-update-per-batch vs the sequential
    trainer's 1-update-per-block-update cadence."""
    trainer = BlockParallelTrainer(dbm, tcfg, periphery=periphery,
                                   devices=devices, impl=impl,
                                   precision=precision,
                                   periphery_lr_scale=periphery_lr_scale)
    return trainer.train(data_iter, rng, params=params, log=log,
                         ckpt_dir=ckpt_dir)
