"""Process start to the window opening: loading, weights drawn from the
seed, compiling or reading the compile cache, warm-up (host clock)."""


def read(run):
    return run.setup_s if run.setup_s > 0 else None
