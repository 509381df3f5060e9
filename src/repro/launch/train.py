"""Distributed training driver.

Wires the mesh + sharding rules into the DiffusionBlocks training loop:

  * --mode db  (default): block-cycling DB training (paper Fig. 3) — each
    step trains one uniformly-sampled block; gradients/optimizer exist for
    L/B units only.
  * --mode e2e: end-to-end backprop baseline.
  * --block-parallel: every pod trains a DIFFERENT block concurrently via
    repro.parallel — blocks share zero gradients, so the pod axis carries no
    optimizer collectives; the shared periphery is reconciled by --periphery
    and per-block checkpoints (repro.checkpoint) are the merge points. With
    fewer devices than blocks the engine degrades to the round-robin scan.

  * --supervise (implied by --resume / --faults): the TrainRunner
    fault-tolerant loop — generational crash-consistent checkpoints in
    --ckpt-dir, per-block anomaly guards with rewind, heartbeats, pod-death
    degradation/re-adoption, bounded restart, and seeded fault injection
    (docs/training.md).

Runs on real local devices (CPU dev: 1 device; tests use
--xla_force_host_platform_device_count to exercise sharding).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs, runtime
from repro.configs import DBConfig, get_config, reduced
from repro.configs.base import TrainConfig
from repro.core import DiffusionBlocksModel
from repro.core.training import make_db_train_step, make_e2e_train_step
from repro.checkpoint import save_block
from repro.data import MarkovLM, HostDataLoader
from repro.launch.mesh import make_host_mesh
from repro.sharding import param_shardings, tokens_sharding


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="reduced config (CPU-feasible); full config needs TPU")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--mode", default="db", choices=["db", "e2e"])
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--block-parallel", action="store_true")
    ap.add_argument("--precision", default="fp32", choices=["fp32", "bf16"],
                    help="mixed-precision policy (repro.precision): fp32 "
                         "masters + bf16 compute + fp32 reductions, or pure "
                         "fp32")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "naive", "chunked", "triangle",
                             "kernels"],
                    help="attention/elementwise implementation; 'kernels' "
                         "routes fwd+bwd through the custom-VJP Pallas "
                         "kernels")
    ap.add_argument("--periphery", default="replicate+psum-mean",
                    help="periphery sync policy for --block-parallel "
                         "(replicate+psum-mean | owner-broadcast | "
                         "freeze-after-warmup)")
    ap.add_argument("--periphery-lr-scale", default=None,
                    help="--block-parallel: compensate the periphery's "
                         "1-update-per-batch cadence ('auto' = scale by the "
                         "block count, or a float; default off)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    # -- fault-tolerant supervisor (repro.launch.trainrunner) --------------
    ap.add_argument("--supervise", action="store_true",
                    help="run under the TrainRunner supervisor: generational "
                         "crash-consistent checkpoints in --ckpt-dir, "
                         "per-block anomaly guards with rewind, heartbeats, "
                         "bounded restart (implied by --resume / --faults)")
    ap.add_argument("--ckpt-every", type=int, default=20,
                    help="supervisor checkpoint cadence (batches in "
                         "--block-parallel, steps in --mode db)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="checkpoint generations to retain")
    ap.add_argument("--resume", action="store_true",
                    help="resume bit-identically from the latest good "
                         "generation in --ckpt-dir")
    ap.add_argument("--faults", default="",
                    help="JSON fault-injection spec, e.g. "
                         "'{\"pod_die\": {\"every\": 50}, "
                         "\"grad_nan\": {\"p\": 0.02}}' "
                         "(hooks: pod_die grad_nan data_stall ckpt_corrupt)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="restart budget for simulated process death "
                         "(--mode db pod_die)")
    ap.add_argument("--pod-restart-after", type=int, default=2,
                    help="batches a dead pod stays down before its block is "
                         "re-adopted (--block-parallel pod_die)")
    args = ap.parse_args()
    runtime.init_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    n_units = DiffusionBlocksModel(cfg, DBConfig(num_blocks=1)).model.n_units
    db = DBConfig(num_blocks=min(args.blocks, n_units), overlap_gamma=0.1)
    dbm = DiffusionBlocksModel(cfg, db)
    tcfg = TrainConfig(steps=args.steps, batch_size=args.batch,
                       seq_len=args.seq, lr=args.lr, seed=args.seed)

    mesh = make_host_mesh(args.model_parallel)
    print(f"mesh: {dict(mesh.shape)} | arch={cfg.name} units={n_units} "
          f"blocks={db.num_blocks} mode={args.mode} "
          f"block_parallel={args.block_parallel}")

    rng = jax.random.PRNGKey(args.seed)
    rng, r0 = jax.random.split(rng)
    with mesh:
        params = dbm.init(r0)
    p_shard = param_shardings(dbm.model.axes(), mesh,
                              jax.eval_shape(lambda: params))
    params = jax.tree_util.tree_map(jax.device_put, params, p_shard)

    lm = MarkovLM(vocab_size=cfg.vocab_size, seed=7)
    t_shard = tokens_sharding(mesh, args.batch)

    supervise = args.supervise or args.resume or bool(args.faults)
    if supervise:
        # fault-tolerant path: TrainRunner owns checkpoints, guards,
        # restarts, and the (cursor-able) data stream
        if args.mode == "e2e":
            raise SystemExit("--supervise covers --mode db and "
                             "--block-parallel only")
        if args.block_parallel and args.model_parallel > 1:
            raise SystemExit(
                "--block-parallel builds its own (pod, data) mesh and does "
                "not compose with --model-parallel yet; drop one of the two")
        import json

        from repro.data import MarkovStream
        from repro.launch.faults import make_injector
        from repro.launch.trainrunner import TrainRunner

        faults = make_injector(json.loads(args.faults) if args.faults
                               else None, seed=args.fault_seed)

        def make_data(cur):
            src = (lm.stream(args.batch, args.seq) if cur is None
                   else MarkovStream.from_cursor(cur))
            return HostDataLoader(src, sharding=t_shard)

        runner = TrainRunner(
            dbm, tcfg,
            mode="block-parallel" if args.block_parallel else "db",
            periphery=args.periphery, impl=args.impl,
            precision=args.precision,
            periphery_lr_scale=args.periphery_lr_scale,
            ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every,
            keep=args.ckpt_keep, faults=faults,
            max_restarts=args.max_restarts,
            pod_restart_after=args.pod_restart_after)
        params, _ = runner.train(make_data, rng, params=params,
                                 resume=args.resume)
        print("supervisor stats:", json.dumps(runner.stats()))
        print("done")
        return

    data = HostDataLoader(lm.iterator(args.batch, args.seq),
                          sharding=t_shard)

    if args.mode == "e2e":
        init_opt, step = make_e2e_train_step(dbm, tcfg, impl=args.impl,
                                             precision=args.precision,
                                             donate=True)
        opt = init_opt(params)
        for it in range(args.steps):
            rng, rs = jax.random.split(rng)
            t0 = time.time()
            params, opt, loss, m = step(params, opt, next(data), rs, None)
            if it % 10 == 0:
                print(f"[e2e] it={it} loss={float(loss):.4f} "
                      f"dt={time.time()-t0:.3f}s")
    elif args.block_parallel:
        # the real thing (repro.parallel): all blocks advance concurrently on
        # a pod-per-block mesh when the devices exist, round-robin otherwise
        if args.model_parallel > 1:
            raise SystemExit(
                "--block-parallel builds its own (pod, data) mesh and does "
                "not compose with --model-parallel yet; drop one of the two")
        from repro.parallel import BlockParallelTrainer
        trainer = BlockParallelTrainer(
            dbm, tcfg, periphery=args.periphery, impl=args.impl,
            precision=args.precision,
            periphery_lr_scale=args.periphery_lr_scale)
        print(f"block-parallel mode={trainer.mode}"
              + (f" mesh={dict(trainer.mesh.shape)}" if trainer.mesh else ""))
        params, _ = trainer.train(data, rng, params=params,
                                  ckpt_dir=args.ckpt_dir or None)
    else:
        steppers, opts = [], []
        for b in range(db.num_blocks):
            io, st = make_db_train_step(dbm, b, tcfg, impl=args.impl,
                                        precision=args.precision, donate=True)
            steppers.append(st)
            opts.append(io(params))
        for it in range(args.steps):
            rng, rb, rs = jax.random.split(rng, 3)
            b = int(jax.random.randint(rb, (), 0, db.num_blocks))
            t0 = time.time()
            params, opts[b], loss, m = steppers[b](params, opts[b],
                                                   next(data), rs, None)
            if it % 10 == 0:
                print(f"[db] it={it} block={b} loss={float(loss):.4f} "
                      f"dt={time.time()-t0:.3f}s")
        if args.ckpt_dir:
            for b, (start, size) in enumerate(dbm.ranges):
                p = save_block(args.ckpt_dir, params, b, start, size,
                               step=args.steps)
                print("saved", p)
    data.close()
    print("done")


if __name__ == "__main__":
    main()
