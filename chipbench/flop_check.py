#!/usr/bin/env python3
"""Check ``harness.flops`` against the compiler and the program's sizes,
without a chip.

    JAX_PLATFORMS=cpu python3 chipbench/flop_check.py

1. Compiles the ar-lm block step (16 x 1024, bf16, the XLA route
   ``impl="chunked"``: the compiler gives Pallas calls no cost) for a
   described v5e with every scan unrolled (``REPRO_SCAN_UNROLL=1``; XLA's
   cost analysis counts a loop body once) and prints its FLOP count beside
   ``train_block_step``. The compiler counts what that route runs: the
   chunked CE's recomputed forward and every tile of the attention mask
   rectangle, which the required count leaves out; both are printed.
2. Checks ``param_count`` and the decode byte counts against the
   parameter tree and the paged KV pool the program builds."""
import json
import os
import sys

os.environ["REPRO_SCAN_UNROLL"] = "1"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import flops  # noqa: E402
from jobs.train_db import program_model, train_config  # noqa: E402


def load(name, kind):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def sizes(tree):
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def main():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core.training import make_db_train_step
    out = {}
    cfg, t = load("ar-lm", "configs"), load("train.db", "traffic")
    B, S = t["batch"], t["seq_len"]
    dbm = program_model(cfg)
    shapes = jax.eval_shape(dbm.init, jax.random.PRNGKey(0))
    out["ar-lm params: tree / param_count"] = [sizes(shapes),
                                               flops.param_count(cfg)]
    req = flops.train_block_step(cfg, B, S)
    x = flops.dims(cfg)
    n_l = x["L"] // x["nb"]
    rect = 3 * n_l * B * 4 * x["H"] * x["hd"] * (2 * S) ** 2
    ce_recompute = 2 * x["d"] * x["V"] * B * S
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda tr: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tr)
    init_opt, step = make_db_train_step(dbm, 0, train_config(t),
                                        impl="chunked", precision="bf16")
    c = step.lower(sds(shapes), sds(jax.eval_shape(init_opt, shapes)),
                   jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one),
                   jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
                   ).compile()
    comp = float(c.cost_analysis().get("flops", float("nan")))
    out["ar-lm block step TFLOP"] = {
        "required (harness.flops)": req["total"] / 1e12,
        "parts": {k: v / 1e12 for k, v in req.items() if k != "total"},
        "compiler, impl=chunked, scans unrolled": comp / 1e12,
        "required - attention + full 2S x 2S rectangle + CE recompute":
            (req["total"] - req["attention"] + rect + ce_recompute) / 1e12}
    o = load("olmo-1b", "configs")
    m = program_model(o)
    osh = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    out["olmo-1b params: tree / param_count"] = [sizes(osh),
                                                 flops.param_count(o)]
    kv = jax.eval_shape(lambda: m.model.init_paged_cache(1, 2, 16, "bf16"))
    page = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(kv)) / 2
    out["olmo-1b KV bytes per token: pool / harness.flops (one read)"] = [
        page / 16, flops.decode_token_kv_bytes(o, 1) / 2]
    lay = sizes(osh["layers"]) - 16 * flops.adaln_params(flops.dims(o))
    out["olmo-1b decode weight bytes per step"] = {
        "harness.flops": flops.decode_step_weight_bytes(o),
        "2 x bf16 tree (probes + commit), minus commit AdaLN, plus readout":
            2 * (2 * sizes(osh["layers"]) - 16 * flops.adaln_params(
                flops.dims(o)) + sizes(osh["cond"])
                + sizes(osh["embed"])) - 2 * sizes(osh["cond"]),
        "layer matmul params": lay}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
