"""Share of its roofline the flash-attention kernel reaches in a training
step: the larger of (attention FLOPs the db_concat mask requires / peak)
and (bytes fwd+bwd must move / HBM bandwidth), times the steps traced,
over the device time of the ``flash_attention*`` kernel calls."""
from harness import flops


def read(run):
    tr = run.trace
    if tr is None:
        return None
    k = tr.op_seconds(r"^flash_attention")
    n = len(tr.program(r"^jit_(step|local_update|shard_map)"))
    if k <= 0 or n == 0:
        return None
    c, B, S = run.cell.config, run.data["batch"], run.data["seq"]
    t_f = flops.train_block_step(c, B, S)["attention"] / \
        run.peaks["bf16_flops_per_s"]
    t_b = flops.flash_attention_bytes(c, B, S) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * n * max(t_f, t_b) / (k / tr.n_devices)
