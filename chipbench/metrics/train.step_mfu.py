"""FLOPs a block step requires (``harness.flops.train_block_step``) times
the block steps run in the traced window, over the step programs' device
time times the chips times the chip's bf16 peak. A block-parallel program
runs one block step per chip, so per chip this is the same share."""
from harness import flops


def read(run):
    tr = run.trace
    if tr is None:
        return None
    times = tr.program(r"^jit_(step|local_update|shard_map)")
    if not times:
        return None
    f = flops.train_block_step(run.cell.config, run.data["batch"],
                               run.data["seq"])["total"]
    return 100.0 * f * len(times) / (sum(times)
                                     * run.peaks["bf16_flops_per_s"])
