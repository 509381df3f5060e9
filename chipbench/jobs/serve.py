"""Open-loop serving through ``launch.serve.ContinuousBatcher``.

Set-up draws the weights on the device from the seed in bfloat16 (the
served dtype), builds the batcher with the deployment's choices from the
mix (slots, prompt and length caps, page pool, precision; the program's
own defaults for chunk size, segment length, page size and route), and
warms its two programs with two short requests that are drained before
the window opens.

The window: every request of the mix (``harness.traffic``) is handed to
the batcher once it is due, between scheduling steps, by the one thread
that drives ``step``. Requests due in the window run to completion after
it closes, for at most ``drain_cap_s``; one that never completes counts
as a miss. Each token is timed when its segment's output reaches the host.

After the window the plain reference recomputes, for a sample drawn from
the seed of the finished requests (the longest among them), the logits
every served token was drawn from: its prompt and served tokens as
context and the noise its denoising chain started from, re-drawn from the
scheduling step's key. The check is the widest gap by which a served
token's reference logit lies below the reference's best."""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import trace, traffic, weights
from harness.bench import Check, Run
from jobs.train_db import program_model

SERVE_KEY_FOLD = 5


def _noise_fn(num_slots: int, d: int, sigma_max: float):
    """z0 of one token: the key of the denoising chain at step ``t`` of the
    segment run with ``rng``, as the decode segment splits it."""
    def one(rng, t, slot):
        def body(_, c):
            r, _rs = jax.random.split(c[0])
            return (r, _rs)
        _, rs = jax.lax.fori_loop(0, t + 1, body, (rng, rng))
        rn, _ = jax.random.split(rs)
        z = sigma_max * jax.random.normal(rn, (num_slots, 1, d))
        return z[slot, 0]
    return jax.jit(jax.vmap(one))


def run(ctx) -> Run:
    from repro.launch.serve import ContinuousBatcher
    cell, t, seed = ctx.cell, ctx.cell.traffic, ctx.seed
    run = Run(cell=cell, device=ctx.device, peaks=ctx.peaks)
    cfg = cell.config
    V = cfg["model"]["vocab_size"]
    dbm = program_model(cfg)
    ref_mod = ctx.reference_module()
    spec = ref_mod.Spec(cfg)
    shapes = jax.eval_shape(dbm.init, jax.random.PRNGKey(0))
    if weights.layout(shapes) != spec.layout():
        run.error = "the program's parameter layout differs from the " \
            "reference's"
        return run
    params = weights.make_tree(shapes, seed, jnp.bfloat16)
    slots = t["slots"]
    cb = ContinuousBatcher(dbm, params, num_slots=slots,
                           max_prompt=t["max_prompt"], max_len=t["max_len"],
                           total_pages=t.get("total_pages"),
                           precision=t["precision"])
    # warm-up: both programs, with requests drained before the window
    wrs = np.random.default_rng([int(seed), 23])
    for n in (t["max_prompt"] // 2, 8):
        cb.submit(wrs.integers(0, V, n, dtype=np.int32),
                  min(t["max_len"] - n, 2 * cb.seg_len))
    wkey = jax.random.fold_in(jnp.asarray(weights.seed_key(seed)), 99)
    cb.run(wkey)
    base = dict(ingest=cb.ingest_dispatches, decode=cb.decode_dispatches,
                steps=cb.steps)

    reqs = traffic.requests(t, seed, ctx.seconds, V)
    pending = collections.deque(reqs)
    rec = {}                      # rid -> per-request record
    segs = []                     # per step(): rng, host times, token ctxs
    state = {"seg": None}
    win_tokens = [0]
    fault_every = 97 if ctx.fault == "altered_token" else 0

    def on_tokens(req, toks):
        now = time.perf_counter() - t_open
        r = rec[req.rid]
        slot = next(s for s, x in enumerate(cb.slot_req) if x is req)
        if r["first"] is None:
            r["first"], r["first_seg"] = now, len(toks)
        r["last"] = now
        seg = segs[state["seg"]]
        seg["kmax"] = max(seg.get("kmax", 0), len(toks))
        seg["ctx"].extend(len(req.prompt) + len(req.out) - len(toks) + i
                          for i in range(len(toks)))
        r["meta"].append((state["seg"], slot, len(toks)))
        if now < ctx.seconds:
            win_tokens[0] += len(toks)
        if fault_every:
            for i in range(len(req.out) - len(toks), len(req.out)):
                if (req.rid * 31 + i) % fault_every == 0:
                    req.out[i] = (req.out[i] + 1) % V

    cb.token_cb = on_tokens
    rng = jax.random.fold_in(jnp.asarray(weights.seed_key(seed)),
                             SERVE_KEY_FOLD)
    rec_tr = trace.Recorder(ctx.trace_dir) if ctx.trace else None
    trace_end = t.get("trace_seconds", 8)
    drain_end = ctx.seconds + t["drain_cap_s"]
    lateness = []
    done = {}
    queued = []                   # (host s, requests waiting for a slot)
    t_open = time.perf_counter()
    run.setup_s = t_open - ctx.t_start
    if rec_tr:
        rec_tr.start()
    while True:
        now = time.perf_counter() - t_open
        if rec_tr and rec_tr.on and now >= trace_end:
            rec_tr.stop()
        with jax.profiler.TraceAnnotation("bench.submit"):
            while pending and pending[0]["due"] <= now:
                q = pending.popleft()
                rid = cb.submit(q["prompt"], q["max_new"])
                lateness.append(time.perf_counter() - t_open - q["due"])
                rec[rid] = {"due": q["due"], "first": None, "last": None,
                            "first_seg": 0, "meta": [], "done": None,
                            "prompt": q["prompt"], "max_new": q["max_new"]}
        if now >= drain_end or (not pending and not cb.has_work()):
            break
        queued.append((now, len(cb.queue)))
        if cb.has_work():
            segs.append({"rng": np.asarray(rng), "t0": now, "ctx": []})
            state["seg"] = len(segs) - 1
            with jax.profiler.TraceAnnotation("bench.step"):
                rng, fin = cb.step(rng)
            segs[-1]["t1"] = time.perf_counter() - t_open
            for r in fin:
                if r.rid in rec and r.error is None and r.done:
                    rec[r.rid]["done"] = segs[-1]["t1"]
                    done[r.rid] = r
        else:
            with jax.profiler.TraceAnnotation("bench.idle_wait"):
                time.sleep(max(0.0, min(pending[0]["due"] - now, 0.05)))
    if rec_tr:
        rec_tr.stop()
    run.window_s = ctx.seconds
    run.memory_peak_bytes = ctx.memory_peak()
    run.attempted = len(rec)
    run.failed = sum(1 for r in rec.values() if r["done"] is None)
    q = np.asarray(queued) if queued else np.zeros((1, 2))
    quarters = [int(q[q[:, 0] <= f * ctx.seconds][-1, 1]) if
                (q[:, 0] <= f * ctx.seconds).any() else 0
                for f in (0.25, 0.5, 0.75, 1.0)]
    late = [r for r in rec.values() if r["done"] is None
            or r["done"] > ctx.seconds]
    run.data.update(backlog={"offered": len(rec),
                             "queued_at_quarters": quarters,
                             "unfinished_at_close": len(late),
                             "never_finished": run.failed})
    run.data.update(requests=rec, segments=segs, window_tokens=win_tokens[0],
                    lateness_s={"median": float(np.median(lateness)),
                                "max": float(np.max(lateness))},
                    trace_seconds=min(trace_end, ctx.seconds),
                    dispatches={"ingest": cb.ingest_dispatches
                                - base["ingest"],
                                "decode": cb.decode_dispatches
                                - base["decode"],
                                "steps": cb.steps - base["steps"]},
                    seg_len=cb.seg_len, slots=slots)
    if ctx.trace:
        run.trace = trace.reduce(ctx.trace_dir, [d.id for d in ctx.devices])
    outs = {rid: np.asarray(r.out, np.int32) for rid, r in done.items()}
    for x in jax.tree_util.tree_leaves((cb.kv, params)):
        x.delete()
    del cb, params

    # ---- the plain reference over a sample of the finished requests ------
    t_ref = time.perf_counter()
    run.checks = check(ctx, spec, ref_mod, rec, segs, outs, slots)
    run.data["reference_s"] = time.perf_counter() - t_ref
    return run


def sample(rec, outs, seed, want_tokens):
    """The longest finished request, then others drawn from the seed,
    until ``want_tokens`` served tokens are in the sample."""
    rids = sorted(outs)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(outs[r]), -r))
    rest = [r for r in rids if r != longest]
    np.random.default_rng([int(seed), 29]).shuffle(rest)
    pick, n = [longest], len(outs[longest])
    for r in rest:
        if n >= want_tokens:
            break
        pick.append(r)
        n += len(outs[r])
    return pick


def check(ctx, spec, ref_mod, rec, segs, outs, slots):
    t = ctx.cell.traffic
    lim = ctx.cell.limits
    name = "served_logit_gap"
    if ctx.control:
        name = "control_logit_gap"
    pick = sample(rec, outs, ctx.seed, t["check_tokens"])
    if not pick:
        return [Check(name, float("inf"), lim.get("served_logit_gap", 0.0))]
    shapes = weights.shapes_of(spec.layout())
    params = weights.make_tree(shapes, ctx.seed, jnp.bfloat16)
    server = ref_mod.Server(spec)
    control = ref_mod.Server(spec, quant=ctx.control) if ctx.control else None
    znoise = _noise_fn(slots, spec.d, spec.s_max)
    worst, agree, total = 0.0, 0, 0
    for rid in pick:
        r, out = rec[rid], outs[rid]
        rngs, ts, sl = [], [], []
        for seg, slot, k in r["meta"]:
            for i in range(k):
                rngs.append(segs[seg]["rng"])
                ts.append(i)
                sl.append(slot)
        n = len(out)
        z0 = np.asarray(znoise(jnp.asarray(np.stack(rngs[:n])),
                               jnp.asarray(ts[:n], jnp.int32),
                               jnp.asarray(sl[:n], jnp.int32)))
        lg = server.logits(params, r["prompt"], out, z0,
                           ctx_len=t["max_len"], out_len=t["output"]["max"])
        if control is not None:
            lc = control.logits(params, r["prompt"], out, z0,
                                ctx_len=t["max_len"],
                                out_len=t["output"]["max"])
            tok = lc.argmax(-1)
        else:
            tok = out
        best = lg.max(-1)
        got = np.take_along_axis(lg, tok[:, None].astype(np.int64), -1)[:, 0]
        worst = max(worst, float(np.max(best - got)))
        agree += int(np.sum(lg.argmax(-1) == tok))
        total += n
    ctx_note = {"requests": len(pick), "tokens": total,
                "top1_agreement": agree / max(total, 1)}
    print(f"[check] sample {ctx_note}", flush=True)
    return [Check(name, worst, lim.get("served_logit_gap", 0.0))]
