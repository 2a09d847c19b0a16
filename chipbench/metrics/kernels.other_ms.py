"""Device time per step of every event that does no products and is no
collective, each by its self time (a ``while`` by its own share, not its
body's again): BatchNorm statistics, elementwise, converts, copies, the
bandwidth kernels (``trace.BANDWIDTH_KERNELS``: the pool's, the
cross-entropy's log-sum-exp), XLA's own custom calls, the optimizer update."""


def read(run):
    t = run["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["other_s_per_step"]
