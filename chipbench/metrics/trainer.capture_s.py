"""Seconds of set-up inside the program's span ``trainer.capture`` (the
op-by-op forward, the symbolic trace and passes, placing the state)."""
from chipbench import program_spans


def read(run):
    return program_spans.select(run)["setup"].get("trainer.capture")
