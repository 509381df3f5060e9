"""Block-parallel training (``parallel.BlockParallelTrainer``): one
``shard_map`` program per batch trains every block on its own chip
(pod = blocks, data = 1) on the same batch, then a ``psum`` averages the
blocks' periphery gradients (``replicate+psum-mean``) for one periphery
update. Set-up, checked steps, window and reference follow
``jobs/train_db.py``; a batch counts as B block updates of tokens."""
from __future__ import annotations

import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, feed, leaves, trace, weights
from harness.bench import Run
from jobs.train_db import markov, program_model, step_keys, train_config


def batch_keys(seed: int, n: int, nb: int) -> np.ndarray:
    """(n, nb, 2): one key per block per batch."""
    k = step_keys(seed, n)
    return np.asarray(jax.vmap(lambda kk: jax.vmap(
        lambda b: jax.random.fold_in(kk, b))(jnp.arange(nb)))(
            jnp.asarray(k)))


def _flat_stacks(tree):
    return jax.tree_util.tree_map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), tree)


def run(ctx) -> Run:
    from repro.parallel import BlockParallelTrainer
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
    rows = B // 2 if ctx.fault == "half_batch" else B
    dev0 = ctx.devices[0]

    if ctx.control is None:
        kw = {"precision": t["precision"], "devices": ctx.devices,
              "periphery": t["periphery"]}
        if "impl" in inspect.signature(BlockParallelTrainer).parameters:
            kw["impl"] = t["impl"]
        tr = BlockParallelTrainer(dbm, train_config(t), **kw)
        if tr.mode != "shard_map":
            run.error = f"expected one block per chip, got mode {tr.mode}"
            return run
        params = weights.make_tree(shapes, seed, jnp.float32)
        st = {"s": tr.init_state(params)}
        del params              # the state may share its buffers
        keys = batch_keys(seed, 1 << 12, nb)
        fd = feed.Feed(markov(cell, seed), B, S)

        def do_step(i):
            with jax.profiler.TraceAnnotation("bench.feed"):
                j, tok = fd.next()
                assert j == i
                tok = jnp.asarray(tok[:rows])
            with jax.profiler.TraceAnnotation("bench.step"):
                s2, losses, _ = tr.step(st["s"], tok, jnp.asarray(keys[i]))
            if ctx.fault != "stale_state":
                st["s"] = s2
            return losses

        prog = {"losses": []}
        for i in range(n_checked):
            prog["losses"].extend(float(x) for x in do_step(i))
            if i == 0:
                s = st["s"]
                g = dict(s.periph_opt.mu,
                         layers=_flat_stacks(s.stack_opt.mu["layers"]))
                prog["grad"] = {k: v / (1 - t["b1"]) for k, v in
                                leaves.leaf_norms(g).items()}
        s = st["s"]
        full = jax.device_put(dict(s.periph,
                                   layers=_flat_stacks(s.stacks["layers"])),
                              dev0)
        p0 = weights.make_tree(shapes, seed, jnp.float32)
        prog["change"] = leaves.change_norms(full, p0)
        del full, p0            # ``full`` may share the state's buffers

        t_open = time.perf_counter()
        run.setup_s = t_open - ctx.t_start
        losses, i = [], n_checked

        def until(deadline):
            nonlocal i
            while time.perf_counter() < deadline:
                losses.append(do_step(i))
                i += 1
        end = t_open + ctx.seconds
        if ctx.trace:
            with trace.record(ctx.trace_dir):
                until(min(end, t_open + t.get("trace_seconds", 8)))
                jax.block_until_ready(st["s"])
        until(end)
        jax.block_until_ready((st["s"], losses))
        run.window_s = time.perf_counter() - t_open
        fd.close()
        lv = np.asarray(jax.device_get(losses), np.float64)
        run.attempted = lv.shape[0] * nb
        run.failed = int((~np.isfinite(lv)).sum())
        run.data.update(steps=lv.shape[0], tokens=lv.shape[0] * nb * B * S,
                        batch=B, seq=S, blocks_per_program=1)
        run.memory_peak_bytes = ctx.memory_peak()
        if ctx.trace:
            run.trace = trace.reduce(ctx.trace_dir,
                                     [d.id for d in ctx.devices])
        for x in jax.tree_util.tree_leaves(st):
            x.delete()
        del st, tr

    t_ref = time.perf_counter()
    ref = reference_readings(ref_mod, spec, cell, seed, n_checked, None)
    if ctx.control is not None:
        prog = reference_readings(ref_mod, spec, cell, seed, n_checked,
                                  ctx.control)
    run.data["reference_s"] = time.perf_counter() - t_ref
    run.data["readings"] = {"program_losses": prog["losses"],
                            "reference_losses": ref["losses"],
                            "excluded": compare.excluded(ref)}
    run.checks = compare.compare(prog, ref, cell.limits)
    return run


def reference_readings(ref_mod, spec, cell, seed, n, quant):
    t = cell.traffic
    B, S = t["batch"], t["seq_len"]
    tr = ref_mod.Trainer(spec, t, quant=quant, rows=t.get("ref_rows", 4))
    shapes = weights.shapes_of(spec.layout())
    params = weights.make_tree(shapes, seed, jnp.float32)
    gen = markov(cell, seed)
    keys = batch_keys(seed, n, spec.nb)
    out = {"losses": [], "step_grads": []}
    states = {}
    for i in range(n):
        params, states, losses, g = tr.parallel_step(
            params, states, gen.batch(i, B, S), jnp.asarray(keys[i]))
        gn = leaves.leaf_norms(g)
        out["losses"].extend(losses)
        out["step_grads"].append(gn)
        if i == 0:
            out["grad"] = gn
    out["change"] = leaves.change_norms(
        params, weights.make_tree(shapes, seed, jnp.float32))
    return out
