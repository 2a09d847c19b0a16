"""Seconds of set-up inside the program's spans ``gluon.initialize`` (the
initializer on every parameter whose shape is known) and ``gluon.set_data``
(a deferred parameter finished on the host, and the copy in); None on a
program without them."""
from chipbench import setup_spans


def read(run):
    return setup_spans.named(run, {"gluon.initialize", "gluon.set_data"})
