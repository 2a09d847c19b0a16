"""Seconds of set-up that no record of the program covers: from the process's
start to the window's first ``trainer.step``, less the union of the intervals
of every record on that step's thread (``setup_spans``)."""
from chipbench import setup_spans


def read(run):
    got = setup_spans.interval(run)
    if got is None:
        return None
    start, end, own = got
    return (end - start) - setup_spans.covered(own, start, end)
