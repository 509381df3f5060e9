"""Per batch of block-parallel training, the time in the periphery
all-reduce during which no other operation runs on that device, averaged
over the chips (device trace: collective op intervals minus the union of
the other ops' intervals)."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    n = len(tr.program(r"^jit_"))
    if n == 0:
        return None
    return 1e3 * tr.exposed_seconds(r"all-reduce|all_reduce|psum") / n
