"""Peak device memory on the fullest chip (live buffers plus XLA's reserved
temporaries, see harness.memory_peak_bytes)."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 if run["memory_peak_bytes"] else None
