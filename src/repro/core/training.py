"""Train-step builders.

``make_db_train_step(dbm, b, …)`` returns a jitted step that computes the
paper's block-local loss (Eq. 6) and takes gradients ONLY for block b's unit
slice plus the shared periphery (embeddings / readout / σ-conditioning /
shared-attention weights in hybrid / encoder in audio). Activations and
optimizer state exist only for those parameters — the B× memory reduction is
structural, not simulated.

``make_e2e_train_step`` is the end-to-end backprop baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro import precision as precision_mod
from repro import tracing
from repro.configs.base import TrainConfig
from repro.core.blocks import DiffusionBlocksModel
from repro.optim import adamw, apply_updates, warmup_cosine

STACK_KEYS = ("layers", "units")


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Per-block anomaly guard (paper §3 independence as a FAULT boundary):
    a non-finite loss/grad-norm or a loss spike skips ONLY the offending
    block's update — its params, AdamW moments, and step counter stay put,
    and (in the block-parallel engine) its periphery gradient contribution
    is masked out of the psum. A spike is ``loss > spike_factor * ewma +
    margin`` once the block's loss EWMA is initialized (first clean step);
    ``rewind_after`` consecutive anomalies tell the supervisor
    (``repro.launch.trainrunner``) to rewind that block alone to its last
    checkpoint generation."""
    spike_factor: float = 8.0
    margin: float = 2.0
    ewma_decay: float = 0.9
    rewind_after: int = 3

    def classify(self, loss, gnorm, ewma, active=True):
        """(ok, new_ewma) — jit-safe scalars. ``ewma < 0`` means
        uninitialized (spike check disarmed); the EWMA only advances on
        clean steps so an anomaly can't drag the baseline toward itself.
        ``active=False`` (a dead pod / masked block) forces not-ok without
        touching the EWMA."""
        finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        spike = (ewma > 0) & (loss > self.spike_factor * ewma + self.margin)
        ok = finite & ~spike & active
        d = self.ewma_decay
        new_ewma = jnp.where(
            ok, jnp.where(ewma < 0, loss, d * ewma + (1 - d) * loss), ewma)
        return ok, new_ewma


@tracing.scope(tracing.BLOCK_VIEW)
def extract_block_view(params: Dict, start: int, size: int) -> Dict:
    """Sub-tree containing ONLY block b's unit slice + shared periphery.
    The view is itself a valid params dict whose stacks have length ``size``
    (apply with unit_range=(0, size))."""
    view = {}
    for k, v in params.items():
        if k in STACK_KEYS:
            view[k] = jax.tree_util.tree_map(
                lambda p: jax.lax.slice_in_dim(p, start, start + size, axis=0),
                v)
        else:
            view[k] = v
    return view


@tracing.scope(tracing.BLOCK_VIEW)
def write_back_block_view(params: Dict, view: Dict, start: int) -> Dict:
    out = {}
    for k, v in params.items():
        if k in STACK_KEYS:
            out[k] = jax.tree_util.tree_map(
                lambda whole, blk: jax.lax.dynamic_update_slice_in_dim(
                    whole, blk.astype(whole.dtype), start, axis=0),
                v, view[k])
        else:
            out[k] = view[k]
    return out


def make_optimizer(tcfg: TrainConfig):
    lr = warmup_cosine(tcfg.lr, tcfg.warmup_steps, tcfg.steps)
    return adamw(lr, tcfg.b1, tcfg.b2, tcfg.eps,
                 weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip)


def make_db_train_step(dbm: DiffusionBlocksModel, b: int, tcfg: TrainConfig,
                       impl: str = "auto", jit: bool = True,
                       donate: bool = False, unit_range=None,
                       precision=None, guard: Optional[GuardConfig] = None):
    """Returns (init_opt_state_fn, step_fn).

    step_fn(params, opt_state_b, tokens, rng, aux_inputs=None)
        -> (params, opt_state_b, loss, metrics)

    ``unit_range`` overrides the block's unit slice (dry-run probes).

    ``impl="kernels"`` runs the block loss fwd+bwd entirely through the
    custom-VJP Pallas kernels; ``precision`` (repro.precision) keeps fp32
    master params and AdamW moments while the loss sees compute-dtype weight
    copies (the cast's transpose accumulates grads back to fp32). ``donate``
    donates the (params, opt_state) buffers to the jitted step so the update
    happens in place — no second copy of the model in HBM.

    ``guard`` (a ``GuardConfig``) switches to the ANOMALY-GUARDED signature:

    step_fn(params, opt_state_b, ewma, tokens, rng, aux_inputs=None,
            loss_mult=1.0) -> (params, opt_state_b, ewma, loss, metrics)

    where ``ewma`` is the block's scalar loss EWMA (pass -1.0 to start), a
    non-finite or spiking loss leaves params AND optimizer state (including
    the step counter) untouched, and ``metrics["ok"]`` reports the verdict.
    ``loss_mult`` scales the loss inside the grad (the ``grad_nan`` fault
    injection point — NaN in, guard catches it). With ``guard=None`` the
    behavior and signature are exactly the historical ones.
    """
    start, size = unit_range if unit_range is not None else dbm.ranges[b]
    pol = precision_mod.get_policy(precision)
    opt_init, opt_update = make_optimizer(tcfg)

    def init_opt(params):
        return opt_init(extract_block_view(params, start, size))

    def grads_of(params, tokens, rng, aux_inputs, loss_mult=None):
        view = extract_block_view(params, start, size)

        def loss_fn(v):
            with tracing.scope(tracing.BLOCK_VIEW):
                vc = precision_mod.cast_params_for_compute(pol, v,
                                                           dbm.cfg.family)
            loss, metrics = dbm.block_loss(vc, b, tokens, rng,
                                           aux_inputs=aux_inputs,
                                           impl=impl, unit_range=(0, size),
                                           precision=pol)
            if loss_mult is not None:
                loss = loss * loss_mult
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(view)
        return view, loss, metrics, grads

    def step(params, opt_state, tokens, rng, aux_inputs=None):
        view, loss, metrics, grads = grads_of(params, tokens, rng, aux_inputs)
        with tracing.scope(tracing.OPTIMIZER):
            updates, opt_state, om = opt_update(grads, opt_state, view)
            view = apply_updates(view, updates)
        params = write_back_block_view(params, view, start)
        metrics = {**metrics, **om}
        return params, opt_state, loss, metrics

    def guarded_step(params, opt_state, ewma, tokens, rng, aux_inputs=None,
                     loss_mult=1.0):
        view, loss, metrics, grads = grads_of(params, tokens, rng,
                                              aux_inputs, loss_mult)
        with tracing.scope(tracing.OPTIMIZER):
            updates, opt2, om = opt_update(grads, opt_state, view)
            view2 = apply_updates(view, updates)
        with tracing.scope(tracing.GUARD):
            ok, ewma = guard.classify(loss, om["grad_norm"], ewma)
            sel = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
            view = jax.tree_util.tree_map(sel, view2, view)
            opt_state = jax.tree_util.tree_map(sel, opt2, opt_state)
        params = write_back_block_view(params, view, start)
        metrics = {**metrics, **om, "ok": ok}
        return params, opt_state, ewma, loss, metrics

    fn = step if guard is None else guarded_step
    if jit:
        fn = jax.jit(fn, donate_argnums=(0, 1) if donate else ())
    return init_opt, fn


def make_e2e_train_step(dbm: DiffusionBlocksModel, tcfg: TrainConfig,
                        impl: str = "auto", jit: bool = True,
                        remat: bool = False, donate: bool = False,
                        precision=None):
    pol = precision_mod.get_policy(precision)
    opt_init, opt_update = make_optimizer(tcfg)

    def step(params, opt_state, tokens, rng, aux_inputs=None):
        def loss_fn(p):
            pc = precision_mod.cast_params_for_compute(pol, p,
                                                       dbm.cfg.family)
            return dbm.e2e_loss(pc, tokens, rng, aux_inputs=aux_inputs,
                                impl=impl, precision=pol)

        if remat:
            loss_fn = jax.checkpoint(loss_fn)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        with tracing.scope(tracing.OPTIMIZER):
            updates, opt_state, om = opt_update(grads, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, loss, {**metrics, **om}

    if jit:
        step = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return opt_init, step


def train_db(dbm: DiffusionBlocksModel, tcfg: TrainConfig, data_iter,
             rng, params=None, log=print, aux_fn=None, parallel=None,
             periphery: str = "replicate+psum-mean", impl: str = "auto",
             precision=None, periphery_lr_scale=None):
    """Block-cycling single-host training driver (paper Fig. 3 right):
    each iteration samples a block uniformly and trains only it.

    ``parallel="blocks"`` routes to ``repro.parallel``: ALL blocks advance
    concurrently (one pod group per block when the host has the devices,
    round-robin otherwise), with the shared periphery reconciled by the
    ``periphery`` sync policy. ``tcfg.steps`` stays the total budget of
    per-block updates in both modes, so histories are comparable.
    ``periphery_lr_scale`` ("auto" = scale by B, or a float) compensates the
    parallel engine's periphery update-count gap: it applies ONE periphery
    update per batch where this sequential loop applies one per block
    update."""
    if parallel == "blocks":
        if aux_fn is not None:
            raise NotImplementedError(
                "aux_fn (modality conditioning) is not supported by the "
                "block-parallel engine yet; use the sequential path")
        from repro.parallel import train_db_parallel
        return train_db_parallel(dbm, tcfg, data_iter, rng, params=params,
                                 log=log, periphery=periphery, impl=impl,
                                 precision=precision,
                                 periphery_lr_scale=periphery_lr_scale)
    if parallel not in (None, "none"):
        raise ValueError(f"unknown parallel mode {parallel!r}")
    rng, r0 = jax.random.split(rng)
    if params is None:
        params = dbm.init(r0)
    steppers, opt_states = [], []
    for b in range(dbm.num_blocks):
        init_opt, step = make_db_train_step(dbm, b, tcfg, impl=impl,
                                            precision=precision)
        steppers.append(step)
        opt_states.append(init_opt(params))
    history = []
    for it in range(tcfg.steps):
        with tracing.span(tracing.BATCH):
            tokens = next(data_iter)
            aux = aux_fn(tokens) if aux_fn else None
            rng, rb, rs = jax.random.split(rng, 3)
            b = int(jax.random.randint(rb, (), 0, dbm.num_blocks))
        with tracing.span(tracing.DISPATCH):
            params, opt_states[b], loss, m = steppers[b](
                params, opt_states[b], tokens, rs, aux)
        with tracing.span(tracing.LOSS_READBACK):
            history.append((it, b, float(loss)))
            if tcfg.log_every and it % tcfg.log_every == 0:
                log(f"[db] it={it} block={b} loss={float(loss):.4f} "
                    f"gn={float(m['grad_norm']):.2f}")
    return params, history


def train_e2e(dbm: DiffusionBlocksModel, tcfg: TrainConfig, data_iter,
              rng, params=None, log=print, aux_fn=None, impl: str = "auto",
              precision=None):
    rng, r0 = jax.random.split(rng)
    if params is None:
        params = dbm.init(r0)
    init_opt, step = make_e2e_train_step(dbm, tcfg, impl=impl,
                                         precision=precision)
    opt_state = init_opt(params)
    history = []
    for it in range(tcfg.steps):
        tokens = next(data_iter)
        aux = aux_fn(tokens) if aux_fn else None
        rng, rs = jax.random.split(rng)
        params, opt_state, loss, m = step(params, opt_state, tokens, rs, aux)
        history.append((it, -1, float(loss)))
        if tcfg.log_every and it % tcfg.log_every == 0:
            log(f"[e2e] it={it} loss={float(loss):.4f}")
    return params, history
