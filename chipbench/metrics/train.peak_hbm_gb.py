"""Peak device memory of the fullest chip of the run, read after the
window: ``memory_stats()`` peak bytes in use plus peak bytes reserved for
the programs' temporaries (``harness.device.footprint``)."""


def read(run):
    if run.trace is None or run.memory_peak_bytes <= 0:
        return None
    return run.memory_peak_bytes / 1e9
