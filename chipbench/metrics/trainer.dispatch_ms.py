"""Median host time to enqueue one step: the harness span around
``trainer.step()`` in the measured window."""
import statistics


def read(run):
    s = run["spans"].get("trainer.step")
    return 1e3 * statistics.median(s) if s else None
