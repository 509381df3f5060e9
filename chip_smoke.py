#!/usr/bin/env python3
"""Smoke test of DiffusionBlocks on a TPU: the main path, once, at the full
width of the paper's §5.4 LM (12 layers, d=768, 12 heads of 64, vocab 32000,
B=4 blocks; ``configs/paper.AR_LM`` / ``AR_DB``), weights random from
``--seed``.

    python chip_smoke.py             # one chip: training + serving
    python chip_smoke.py --chips 4   # four chips: block-parallel training only

One chip, three phases in one process:

  device    the first JAX device must be a TPU — there is no CPU fallback
  training  ``make_db_train_step`` (the step factory behind ``train_db`` and
            ``launch/train.py``) with ``impl="kernels"``, ``precision="bf16"``
            on 8x1024 tokens: every block takes TRAIN_STEPS steps, all losses
            finite; the compiled step must hold Mosaic kernels
            (``tpu_custom_call``); block 0's first loss and grad norm must
            match the XLA reference step (``impl="chunked"``) within
            LOSS_RTOL and GRAD_RTOL
  serving   the trained parameters behind ``ContinuousBatcher`` with
            ``impl="kernels"`` (flash-prefill + flash-decode) answer 8 ragged
            requests (prompts 64-256 tokens, 32 new tokens each); the same
            requests through the reference route (``impl="auto"``) must agree:
            the engine's next-token logits after prefill within LOGIT_TOL,
            greedy tokens identical in at least MIN_IDENTICAL requests and
            none diverging before new token MIN_DIVERGE

``--chips 4`` runs only the block-parallel phase: ``BlockParallelTrainer`` on
four chips (pod=4, data=1, shard_map) against the round-robin schedule on one
chip, per-block losses within LOSS_RTOL, with each block's stack placement
printed.

Every phase prints its compile and run times; any failed check exits
non-zero. The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp

BATCH, SEQ = 8, 1024              # ~8k training tokens per step
TRAIN_STEPS = 2                   # steps per block
N_REQ, PROMPT_MIN, PROMPT_MAX, MAX_NEW = 8, 64, 256, 32
PAGE, CHUNK, SEG = 16, 64, 16
PAR_BATCHES = 3                   # --chips 4: batches per trainer
# Tolerances (see CHANGES.md), each with the reading of the first v5e run.
# bf16 keeps 8 mantissa bits (one ulp is 2^-8 = 0.4% relative); the kernel
# and reference routes differ only in how attention rounds and sums, and an
# 8k-token mean averages that out.
LOSS_RTOL = 1e-4                  # scalar CE loss (measured 1.23e-6)
GRAD_RTOL = 1e-3                  # global grad norm (measured < 1e-5)
LOGIT_TOL = 1e-2                  # max |dlogit| / max |logit| (measured 8.6e-4)
# Greedy tokens: a near-tied argmax may flip on rounding and then the two
# routes see different contexts, so at most N_REQ - MIN_IDENTICAL requests
# may diverge, and none before token MIN_DIVERGE (measured: 7/8 identical,
# the other first differs at token 27).
MIN_IDENTICAL = N_REQ - 2
MIN_DIVERGE = MAX_NEW // 4


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def check_mosaic(text: str, what: str):
    """A Pallas kernel that ran in interpret mode leaves no custom call."""
    check("tpu_custom_call" in text, f"{what}: no Mosaic kernel "
          "(tpu_custom_call) in the program — kernels did not compile")


def log(msg: str):
    print(msg, flush=True)


def flops(compiled) -> float:
    """The compiler's FLOP count for a compiled program."""
    return float((compiled.cost_analysis() or {}).get("flops", float("nan")))


def build_model(seed: int):
    import jax
    from repro.configs.paper import AR_DB, AR_LM
    from repro.core import DiffusionBlocksModel
    dbm = DiffusionBlocksModel(AR_LM, AR_DB)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(dbm.init)(jax.random.PRNGKey(seed)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"[model] {AR_LM.name}: {AR_LM.n_layers} layers d={AR_LM.d_model} "
        f"heads={AR_LM.n_heads}x{AR_LM.head_dim} vocab={AR_LM.vocab_size} "
        f"blocks={dbm.num_blocks} | {n / 1e6:.1f}M params fp32 | init "
        f"{time.perf_counter() - t0:.2f}s")
    return dbm, params


def training_tokens(vocab: int, seed: int):
    import jax.numpy as jnp
    import numpy as np
    from repro.data import MarkovLM
    lm = MarkovLM(vocab_size=vocab, seed=7)
    return lm, jnp.asarray(lm.sample(np.random.RandomState(seed), BATCH, SEQ),
                           jnp.int32)


def train_config(steps: int):
    from repro.configs.base import TrainConfig
    return TrainConfig(steps=steps, batch_size=BATCH, seq_len=SEQ, lr=3e-4,
                       warmup_steps=1, log_every=0)


def phase_training(dbm, params, tokens, seed: int):
    """Every block trains through the Pallas kernels; block 0's first loss
    is checked against the XLA reference step on the same inputs."""
    import jax
    from repro.core.training import make_db_train_step
    tcfg = train_config(TRAIN_STEPS * dbm.num_blocks)
    rng = jax.random.PRNGKey(seed + 1)
    losses = {}
    for b in range(dbm.num_blocks):
        init_opt, step = make_db_train_step(dbm, b, tcfg, impl="kernels",
                                            precision="bf16", donate=True)
        opt = init_opt(params)
        rng, rs = jax.random.split(rng)
        t0 = time.perf_counter()
        compiled = step.lower(params, opt, tokens, rs).compile()
        t_compile = time.perf_counter() - t0
        if b == 0:
            check_mosaic(compiled.as_text(), "training step")
            log("[train] compiled step holds tpu_custom_call (Mosaic "
                "kernels)")
            _, ref_step = make_db_train_step(dbm, b, tcfg, impl="chunked",
                                             precision="bf16")
            t0 = time.perf_counter()
            ref_c = ref_step.lower(params, opt, tokens, rs).compile()
            t_ref_c = time.perf_counter() - t0
            t0 = time.perf_counter()
            _, _, ref_loss, ref_m = jax.block_until_ready(
                ref_c(params, opt, tokens, rs))
            t_ref_run = time.perf_counter() - t0
            ref_loss, ref_gn = float(ref_loss), float(ref_m["grad_norm"])
            log(f"[train] compiler FLOPs per block step: reference "
                f"{flops(ref_c) / 1e12:.4f} TFLOP, kernels route "
                f"{flops(compiled) / 1e12:.4f} TFLOP (Pallas calls carry "
                f"no cost estimate, so they count 0) | reference step "
                f"first run {t_ref_run:.4f}s")
        block_losses, t_run = [], []
        for s in range(TRAIN_STEPS):
            t0 = time.perf_counter()
            params, opt, loss, m = jax.block_until_ready(
                compiled(params, opt, tokens, rs))
            t_run.append(time.perf_counter() - t0)
            block_losses.append(float(loss))
            if b == 0 and s == 0:
                ker_gn = float(m["grad_norm"])
            rng, rs = jax.random.split(rng)
        losses[b] = block_losses
        log(f"[train] block {b}: compile {t_compile:.2f}s | step run "
            + ", ".join(f"{t:.4f}s" for t in t_run)
            + " | losses " + ", ".join(f"{x:.5f}" for x in block_losses))
        check(all(math.isfinite(x) for x in block_losses),
              f"block {b}: non-finite loss {block_losses}")
        if b == 0:
            rel = abs(block_losses[0] - ref_loss) / abs(ref_loss)
            grel = abs(ker_gn - ref_gn) / abs(ref_gn)
            log(f"[train] block 0 kernels vs reference (impl=chunked, "
                f"compile {t_ref_c:.2f}s): loss {block_losses[0]:.6f} vs "
                f"{ref_loss:.6f} (rel {rel:.2e}, limit {LOSS_RTOL:.0e}) | "
                f"grad norm {ker_gn:.7f} vs {ref_gn:.7f} (rel {grel:.2e}, "
                f"limit {GRAD_RTOL:.0e})")
            check(rel <= LOSS_RTOL, f"kernels loss differs from the "
                  f"reference by {rel:.3e} > {LOSS_RTOL}")
            check(grel <= GRAD_RTOL, f"kernels grad norm differs from the "
                  f"reference by {grel:.3e} > {GRAD_RTOL}")
        del opt
    log(f"[train] all {dbm.num_blocks} blocks trained, "
        f"{TRAIN_STEPS * dbm.num_blocks} steps, losses finite: "
        + json.dumps({str(k): [round(x, 5) for x in v]
                      for k, v in losses.items()}))
    return params


def serving_requests(lm, seed: int):
    import numpy as np
    rs = np.random.RandomState(seed + 2)
    plens = np.linspace(PROMPT_MIN, PROMPT_MAX, N_REQ).astype(int)
    rs.shuffle(plens)
    return [lm.sample(rs, 1, int(n))[0].astype(np.int32) for n in plens]


def _batcher(dbm, params, impl: str):
    from repro.launch.serve import ContinuousBatcher
    return ContinuousBatcher(dbm, params, num_slots=N_REQ, page_size=PAGE,
                             max_prompt=PROMPT_MAX,
                             max_len=PROMPT_MAX + MAX_NEW, seg_len=SEG,
                             precision="bf16", impl=impl, chunk_size=CHUNK)


def serve(dbm, params, prompts, impl: str, seed: int):
    """Answer the requests through a fresh ContinuousBatcher; returns the
    generated tokens per request and the batcher."""
    import jax
    cb = _batcher(dbm, params, impl)
    for p in prompts:
        cb.submit(p, MAX_NEW)
    t0 = time.perf_counter()
    done = cb.run(jax.random.PRNGKey(seed + 3))
    dt = time.perf_counter() - t0
    check(len(done) == len(prompts) and all(
        r.error is None and len(r.out) == MAX_NEW for r in done),
        f"impl={impl}: not every request was answered in full")
    return [list(r.out) for r in done], cb, dt


def next_token_logits(cb, params, prompts, impl: str, seed: int):
    """The serving engine's next-token logits (N, vocab) after prefilling the
    requests: ``DecodeEngine.next_token_logits``, the logits its decode
    programs sample from. On the kernel route, the same call lowered as one
    program must hold Mosaic kernels."""
    import jax
    import numpy as np
    buf = np.zeros((len(prompts), PROMPT_MAX), np.int32)
    plens = np.array([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        buf[i, :len(p)] = p
    rng = jax.random.PRNGKey(seed + 4)

    def call(params, buf, plens, rng):
        return cb.eng.next_token_logits(params, buf, rng,
                                        prompt_lengths=plens, page_size=PAGE)

    if impl == "kernels":
        check_mosaic(jax.jit(call).lower(params, buf, plens, rng).as_text(),
                     "serving prefill + next-token logits")
    return np.asarray(call(params, buf, plens, rng))


def token_divergence(ref, got):
    """Per request, the first new-token position where the greedy outputs
    of the two routes differ (None where they are identical)."""
    return [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
            for a, b in zip(ref, got)]


def phase_serving(dbm, params, lm, seed: int):
    import numpy as np
    prompts = serving_requests(lm, seed)
    log(f"[serve] {len(prompts)} requests, prompt lengths "
        f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each, "
        f"{N_REQ} slots, pages of {PAGE}, chunks of {CHUNK}")
    outs = {}
    for impl in ("kernels", "auto"):
        out, cb, t_cold = serve(dbm, params, prompts, impl, seed)
        out2, cb, t_warm = serve(dbm, params, prompts, impl, seed)
        check(out == out2, f"impl={impl}: a warm rerun changed the output")
        log(f"[serve] impl={impl}: cold run (compile + run) {t_cold:.2f}s, "
            f"warm run {t_warm:.3f}s | "
            f"dispatches {cb.ingest_dispatches} ingest + "
            f"{cb.decode_dispatches} decode per run")
        t0 = time.perf_counter()
        logits = next_token_logits(cb, params, prompts, impl, seed)
        log(f"[serve] impl={impl}: prefill + next-token logits "
            f"{time.perf_counter() - t0:.2f}s (incl. compile)")
        outs[impl] = (out, logits)
    (ker, lk), (ref, lr) = outs["kernels"], outs["auto"]
    check(bool(np.isfinite(lk).all() and np.isfinite(lr).all()),
          "non-finite logits")
    scale = np.maximum(np.abs(lr).max(axis=1), 1e-6)
    err = np.abs(lk - lr).max(axis=1) / scale
    top1 = float(np.mean(lk.argmax(1) == lr.argmax(1)))
    log(f"[serve] next-token logits kernels vs reference: max rel err "
        f"{err.max():.3e} (limit {LOGIT_TOL:.0e}), per request "
        f"{[float(f'{e:.2e}') for e in err]}, top-1 agreement {top1:.3f}")
    check(float(err.max()) <= LOGIT_TOL,
          f"kernel logits differ from the reference by {err.max():.3e}")
    first = token_divergence(ref, ker)
    exact = first.count(None)
    early = min((k for k in first if k is not None), default=None)
    log(f"[serve] greedy tokens kernels vs reference: {exact}/{len(ref)} "
        f"requests identical (limit {MIN_IDENTICAL}), first divergence per "
        f"request {first} (limit: none before token {MIN_DIVERGE})")
    check(exact >= MIN_IDENTICAL, f"only {exact}/{len(ref)} requests "
          f"generate identical tokens through both routes")
    check(early is None or early >= MIN_DIVERGE, f"greedy tokens diverge "
          f"at new token {early} < {MIN_DIVERGE}")


def block_devices(trainer, state):
    """Which device holds each block's slice of the stacked layers."""
    import jax
    leaf = jax.tree_util.tree_leaves(state.stacks)[0]
    where = {}
    for dev, idx in leaf.sharding.devices_indices_map(leaf.shape).items():
        blocks = range(trainer.B)[idx[0]]
        for b in blocks:
            where.setdefault(b, set()).add(f"{dev.platform}:{dev.id}")
    return {b: sorted(v) for b, v in sorted(where.items())}


def phase_block_parallel(dbm, params, tokens, seed: int):
    import jax
    import numpy as np
    from repro.parallel import BlockParallelTrainer
    tcfg = train_config(PAR_BATCHES * dbm.num_blocks)
    rngs = [jax.random.split(jax.random.PRNGKey(seed + 10 + i),
                             dbm.num_blocks) for i in range(PAR_BATCHES)]
    devs = jax.devices()
    runs = {}
    for name, devices in (("shard_map", devs[:dbm.num_blocks]),
                          ("round_robin", devs[:1])):
        tr = BlockParallelTrainer(dbm, tcfg, impl="kernels",
                                  precision="bf16", devices=devices)
        check(tr.mode == name, f"expected mode {name}, got {tr.mode}")
        state = tr.init_state(params)
        place = block_devices(tr, state)
        log(f"[par] {name}: mesh "
            f"{dict(tr.mesh.shape) if tr.mesh is not None else None} | "
            f"block stacks on {place}")
        if name == "shard_map":
            check(len({d for v in place.values() for d in v})
                  == dbm.num_blocks and all(len(v) == 1
                                            for v in place.values()),
                  f"shard_map must put each block on its own chip: {place}")
        losses, times = [], []
        for i in range(PAR_BATCHES):
            t0 = time.perf_counter()
            state, l, _ = jax.block_until_ready(
                tr.step(state, tokens, rngs[i]))
            times.append(time.perf_counter() - t0)
            losses.append(np.asarray(l, np.float64))
        losses = np.stack(losses)
        log(f"[par] {name}: batch times (first includes compile) "
            + ", ".join(f"{t:.3f}s" for t in times)
            + f" | per-block losses {np.round(losses, 5).tolist()}")
        check(bool(np.isfinite(losses).all()), f"{name}: non-finite loss")
        runs[name] = losses
    rel = np.abs(runs["shard_map"] - runs["round_robin"]) / np.abs(
        runs["round_robin"])
    log(f"[par] shard_map vs round_robin per-block losses: max rel "
        f"{rel.max():.3e} (limit {LOSS_RTOL:.0e})")
    check(float(rel.max()) <= LOSS_RTOL, "block-parallel losses disagree")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro import runtime
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
                 "this script only runs on the chip")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} device(s)")
    cache_dir = runtime.init_compile_cache()
    warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devs)} | jax "
        f"{jax.__version__} | compile cache {cache_dir} "
        f"({'warm' if warm else 'cold'})")

    t_all = time.perf_counter()
    dbm, params = build_model(args.seed)
    lm, tokens = training_tokens(dbm.cfg.vocab_size, args.seed)
    try:
        if args.chips == 4:
            phase_block_parallel(dbm, params, tokens, args.seed)
        else:
            params = phase_training(dbm, params, tokens, args.seed)
            phase_serving(dbm, params, lm, args.seed)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    log(f"[done] all phases passed in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
