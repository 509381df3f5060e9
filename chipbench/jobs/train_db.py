"""Block-wise training as ``core.training.train_db`` runs it: each step
picks one block uniformly (from the seed) and runs that block's jitted
step (``make_db_train_step``) on a fresh batch from the input feed.

Set-up builds the four block steps once (compiled ahead of time, from the
persistent cache after the first run), draws the weights on the device
from the seed, and drives the first ``loss_steps`` steps through the same
call and feed as the window; the plain reference follows those steps
after the window (``harness.compare``): the loss of each, the first
step's gradient, and the change of the parameters after the first
``checked_steps``. The window then runs the same
objects for ``--seconds``; ``train_tokens_per_s`` counts the tokens of
every block update completed in it."""
from __future__ import annotations

import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, feed, leaves, trace, weights
from harness.bench import Run

KEY_FOLD = 2          # step keys: fold_in(fold_in(seed key, 2), step)


def program_model(config: dict):
    from repro.configs.base import DBConfig, ModelConfig
    from repro.core import DiffusionBlocksModel
    m = dict(config["model"])
    return DiffusionBlocksModel(ModelConfig(**m),
                                DBConfig(**config["diffusion_blocks"]))


def train_config(t: dict):
    from repro.configs.base import TrainConfig
    return TrainConfig(steps=t["schedule_steps"], batch_size=t["batch"],
                       seq_len=t["seq_len"], lr=t["lr"],
                       warmup_steps=t["warmup_steps"],
                       weight_decay=t["weight_decay"], b1=t["b1"],
                       b2=t["b2"], eps=t["eps"], grad_clip=t["grad_clip"],
                       log_every=0)


def step_keys(seed: int, n: int) -> np.ndarray:
    base = jax.random.fold_in(jnp.asarray(weights.seed_key(seed)), KEY_FOLD)
    return np.asarray(jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(n)))


def block_order(seed: int, nb: int, n: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 3]).integers(0, nb, n)


def markov(cell, seed):
    d = cell.traffic["data"]
    return feed.Markov(cell.config["model"]["vocab_size"], d["branching"],
                       d["zipf"], seed)


def make_steps(dbm, tcfg, t: dict, donate: bool):
    from repro.core.training import make_db_train_step
    kw = {"precision": t["precision"], "donate": donate}
    if "impl" in inspect.signature(make_db_train_step).parameters:
        kw["impl"] = t["impl"]
    return [make_db_train_step(dbm, b, tcfg, **kw)
            for b in range(dbm.num_blocks)]


def run(ctx) -> Run:
    cell, t, seed = ctx.cell, ctx.cell.traffic, ctx.seed
    run = Run(cell=cell, device=ctx.device, peaks=ctx.peaks)
    dbm = program_model(cell.config)
    ref_mod = ctx.reference_module()
    spec = ref_mod.Spec(cell.config)
    shapes = jax.eval_shape(dbm.init, jax.random.PRNGKey(0))
    if weights.layout(shapes) != spec.layout():
        run.error = "the program's parameter layout differs from the " \
            "reference's"
        return run
    B, S, nb = t["batch"], t["seq_len"], dbm.num_blocks
    n_checked = t["checked_steps"]
    n_loss = max(n_checked, t.get("loss_steps", n_checked))
    tcfg = train_config(t)
    rows = B // 2 if ctx.fault == "half_batch" else B

    if ctx.control is None:
        params = weights.make_tree(shapes, seed, jnp.float32)
        steps = make_steps(dbm, tcfg, t, donate=ctx.fault != "stale_state")
        opts = [jax.jit(init)(params) for init, _ in steps]
        tok0 = jax.ShapeDtypeStruct((rows, S), jnp.int32)
        key0 = jax.ShapeDtypeStruct((2,), jnp.uint32)
        compiled = [st.lower(params, opts[b], tok0, key0).compile()
                    for b, (_, st) in enumerate(steps)]
        run.data["compiled_memory"] = [compiled_memory(c) for c in compiled]
        order = block_order(seed, nb, 1 << 16)
        keys = step_keys(seed, 1 << 16)
        fd = feed.Feed(markov(cell, seed), B, S)
        state = {"params": params, "opts": opts}

        def do_step(i):
            with jax.profiler.TraceAnnotation("bench.feed"):
                j, tok = fd.next()
                assert j == i
                if rows != B:
                    tok = tok[:rows]
                tok = jnp.asarray(tok)
            b = int(order[i])
            with jax.profiler.TraceAnnotation("bench.step"):
                p, o, loss, _ = compiled[b](state["params"],
                                            state["opts"][b], tok, keys[i])
            if ctx.fault != "stale_state":
                state["params"], state["opts"][b] = p, o
            return loss

        prog = {"losses": []}
        for i in range(n_loss):
            prog["losses"].append(float(do_step(i)))
            if i == 0:
                b0 = int(order[0])
                start = dbm.ranges[b0][0]
                mu = state["opts"][b0].mu
                prog["grad"] = {k: v / (1 - t["b1"]) for k, v in
                                leaves.leaf_norms(mu, start).items()}
            if i == n_checked - 1:
                p0 = weights.make_tree(shapes, seed, jnp.float32)
                prog["change"] = leaves.change_norms(state["params"], p0)
                jax.tree_util.tree_map(lambda x: x.delete(), p0)
                del p0

        # ---- the window ------------------------------------------------
        t_open = time.perf_counter()
        run.setup_s = t_open - ctx.t_start
        losses, inflight = [], []
        i = n_loss

        def until(deadline):
            nonlocal i
            while time.perf_counter() < deadline:
                loss = do_step(i)
                losses.append(loss)
                inflight.append(loss)
                i += 1
                if len(inflight) > 2:
                    inflight.pop(0).block_until_ready()
        end = t_open + ctx.seconds
        if ctx.trace:
            with trace.record(ctx.trace_dir):
                until(min(end, t_open + t.get("trace_seconds", 8)))
                jax.block_until_ready(state["params"])
        until(end)
        jax.block_until_ready((state["params"], losses))
        run.window_s = time.perf_counter() - t_open
        fd.close()
        lv = np.asarray(jax.device_get(losses), np.float64)
        run.attempted, run.failed = len(lv), int((~np.isfinite(lv)).sum())
        run.data.update(steps=len(lv), tokens=len(lv) * B * S,
                        batch=B, seq=S, blocks=order[n_loss:i].tolist())
        run.memory_peak_bytes = ctx.memory_peak()
        if ctx.trace:
            run.trace = trace.reduce(ctx.trace_dir,
                                     [d.id for d in ctx.devices])
        for x in jax.tree_util.tree_leaves(state):
            x.delete()
        del state, compiled, opts, steps

    # ---- the plain reference follows the checked steps --------------------
    t_ref = time.perf_counter()
    ref = reference_readings(ref_mod, spec, cell, seed, n_loss, n_checked,
                             None)
    if ctx.control is not None:
        prog = reference_readings(ref_mod, spec, cell, seed, n_loss,
                                  n_checked, ctx.control)
        run.setup_s = 0.0
    run.data["reference_s"] = time.perf_counter() - t_ref
    run.data["readings"] = {"program_losses": prog["losses"],
                            "reference_losses": ref["losses"],
                            "excluded": compare.excluded(ref)}
    run.checks = compare.compare(prog, ref, cell.limits)
    return run


def compiled_memory(c) -> dict:
    """The compiler's memory analysis of one compiled step, in bytes."""
    m = c.memory_analysis()
    return {k: int(getattr(m, k + "_size_in_bytes", 0))
            for k in ("temp", "argument", "output", "alias")}


def reference_readings(ref_mod, spec, cell, seed, n, n_checked, quant):
    """The reference over the first ``n`` steps: every loss; the first
    step's gradient; the change after ``n_checked`` steps, with the
    gradients of those steps (for the rule that leaves a leaf out)."""
    t = cell.traffic
    B, S = t["batch"], t["seq_len"]
    tr = ref_mod.Trainer(spec, t, quant=quant, rows=t.get("ref_rows", 4))
    shapes = weights.shapes_of(spec.layout())
    params = weights.make_tree(shapes, seed, jnp.float32)
    gen = markov(cell, seed)
    order = block_order(seed, spec.nb, n)
    keys = step_keys(seed, n)
    out = {"losses": [], "step_grads": []}
    states = {}
    for i in range(n):
        b = int(order[i])
        params, states, loss, g = tr.step(params, states, b,
                                          gen.batch(i, B, S), keys[i])
        out["losses"].append(loss)
        if i < n_checked:
            out["step_grads"].append(leaves.leaf_norms(g, spec.ranges[b][0]))
        if i == 0:
            out["grad"] = out["step_grads"][0]
        if i == n_checked - 1:
            out["change"] = leaves.change_norms(
                params, weights.make_tree(shapes, seed, jnp.float32))
    return out
