"""1 - union of device-op intervals over the traced window, on the chip that
was busiest."""


def read(run):
    t = run["trace"]
    if not t or not run["traced_window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_fullest_s"] / run["traced_window_s"])
