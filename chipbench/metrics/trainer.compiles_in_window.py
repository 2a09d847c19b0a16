"""XLA backend compiles inside the measured window (``jit_hooks``'s counter
after minus before). Must read 0. A counter that never moved during set-up
is not live and is not read."""


def read(run):
    if run["compiles_setup"] <= 0:
        return None
    return run["compiles_window"]
