"""Device time per step of every event outside the convolution categories and
the collectives: BatchNorm statistics, elementwise, converts, copies, pooling,
the optimizer update."""


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["other_s_per_step"]
