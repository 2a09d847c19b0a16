"""Seconds of set-up inside the program's span ``mxnet_tpu.import`` (the
package's import and each of its lazy submodules'); None on a program
without the span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.named(run, {"mxnet_tpu.import"})
