"""Seconds of set-up inside XLA backend compiles: the program's ``jit.compile``
records (a load from the persistent cache counts: the event fires on hits
too)."""
from chipbench import program_spans


def read(run):
    return program_spans.select(run)["setup"].get("jit.compile")
