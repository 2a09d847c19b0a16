"""Median time inside the program's span ``trainer.enqueue`` over the window's
steps: the call of the compiled step, which is enqueue cost, or back-pressure
once the device's queue is full."""
import statistics

from chipbench import program_spans


def read(run):
    s = [st["trainer.enqueue"] for st in program_spans.select(run)["steps"]
         if "trainer.enqueue" in st]
    return 1e3 * statistics.median(s) if s else None
