"""Operations and bytes that the algorithm requires, from a configuration's
shapes. Recomputation is not counted (not the chunked CE's checkpoint, not
flash attention's backward recompute), nor work on padding, masked tiles or
inactive slots: only what the mathematics needs.

A "layer" is pre-norm attention + SwiGLU MLP with an AdaLN head (d -> 6d);
matrix products count 2 FLOPs per multiply-add; a backward pass counts
twice its forward (dX and dW)."""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    m, db = cfg["model"], cfg["diffusion_blocks"]
    d, H, KV = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    return dict(L=m["n_layers"], d=d, H=H, KV=KV, hd=hd, ff=m["d_ff"],
                V=m["vocab_size"], nb=db["num_blocks"], cd=db["cond_dim"],
                tied=m.get("tie_embeddings", False))


def layer_matmul_params(x: dict) -> int:
    """Weights of one layer's matrix products, AdaLN head excluded."""
    d, hd = x["d"], x["hd"]
    return d * (x["H"] + 2 * x["KV"]) * hd + x["H"] * hd * d + 3 * d * x["ff"]


def adaln_params(x: dict) -> int:
    return x["d"] * 6 * x["d"] + 6 * x["d"]


def cond_params(x: dict) -> int:
    return x["cd"] * x["d"] + x["d"] * x["d"]


# ---------------------------------------------------------------------------
# training: one block step (paper Eq. 6, AR adapter, concat stream)
# ---------------------------------------------------------------------------

def db_concat_pairs(S: int) -> int:
    """(query, key) pairs the db_concat mask allows in one 2S stream:
    clean i sees clean j <= i, noisy i sees clean j < i and itself."""
    return S * (S + 1) // 2 + S * (S - 1) // 2 + S


def train_block_step(cfg: dict, batch: int, seq: int) -> dict:
    """FLOPs of one block update on ``batch`` x ``seq`` tokens, fwd+bwd,
    split by part; ``attention`` is the score and value products alone
    (the flash kernel's share)."""
    x = dims(cfg)
    n_l = x["L"] // x["nb"]
    stream = batch * 2 * seq
    fb = 3                                     # forward + backward
    layers = fb * n_l * (2 * layer_matmul_params(x) * stream
                         + 2 * adaln_params(x) * batch)
    attention = fb * n_l * batch * 4 * x["H"] * x["hd"] * db_concat_pairs(seq)
    readout = fb * 2 * x["d"] * x["V"] * batch * seq
    cond = fb * 2 * cond_params(x) * batch
    return {"layers": layers, "attention": attention, "readout": readout,
            "cond": cond, "total": layers + attention + readout + cond}


def flash_attention_bytes(cfg: dict, batch: int, seq: int,
                          itemsize: int = 2) -> int:
    """HBM bytes the flash-attention calls of one block step must move:
    forward reads q, k, v and writes o (+ fp32 row statistics); backward
    reads q, k, v, o, dO (+ statistics) and writes dq, dk, dv."""
    x = dims(cfg)
    n_l = x["L"] // x["nb"]
    T = batch * 2 * seq
    q = T * x["H"] * x["hd"] * itemsize
    kv = T * x["KV"] * x["hd"] * itemsize
    stats = T * x["H"] * 4
    fwd = q + 2 * kv + q + stats
    bwd = (q + 2 * kv + q + q + stats) + (q + 2 * kv)
    return n_l * (fwd + bwd)


# ---------------------------------------------------------------------------
# serving: one generated token of one slot (probes + commit + readout)
# ---------------------------------------------------------------------------

def decode_token_flops(cfg: dict, ctx: int, steps_per_block: int = 1) -> int:
    """One slot's generated token at context length ``ctx``: the denoising
    probes (every block, ``steps_per_block`` each, AdaLN heads included),
    the L-layer commit, and the readout."""
    x = dims(cfg)
    per_layer = 2 * layer_matmul_params(x)
    attn = 4 * x["H"] * x["hd"] * (ctx + 1)
    probes = steps_per_block * (x["L"] * (per_layer + attn
                                          + 2 * adaln_params(x))
                                + x["nb"] * 2 * cond_params(x))
    commit = x["L"] * (per_layer + attn)
    readout = 2 * x["d"] * x["V"]
    return probes + commit + readout


def decode_step_weight_bytes(cfg: dict, steps_per_block: int = 1,
                             itemsize: int = 2) -> int:
    """Weights one decode step must read, whatever its batch: every layer
    (with its AdaLN head) for the probes, every layer again for the commit,
    the σ-embedding MLP, and the readout matrix."""
    x = dims(cfg)
    probes = steps_per_block * x["L"] * (layer_matmul_params(x)
                                         + adaln_params(x))
    commit = x["L"] * layer_matmul_params(x)
    return itemsize * (probes + commit + cond_params(x) + x["d"] * x["V"])


def decode_token_kv_bytes(cfg: dict, ctx: int, itemsize: int = 2) -> int:
    """KV one slot's token must read at context ``ctx``: every layer's keys
    and values, once for the probe and once for the commit."""
    x = dims(cfg)
    return 2 * x["L"] * 2 * ctx * x["KV"] * x["hd"] * itemsize


def prefill_token_flops(cfg: dict, ctx: int) -> int:
    """One prompt token committed at position ``ctx`` (all L layers)."""
    x = dims(cfg)
    return x["L"] * (2 * layer_matmul_params(x)
                     + 4 * x["H"] * x["hd"] * (ctx + 1))


def param_count(cfg: dict) -> int:
    x = dims(cfg)
    emb = x["V"] * x["d"] * (1 if x["tied"] else 2)
    norms = 0 if cfg["model"]["norm"] == "nonparam_ln" else \
        (2 * x["L"] + 1) * x["d"]
    return (emb + norms + cond_params(x)
            + x["L"] * (layer_matmul_params(x) + adaln_params(x)))
