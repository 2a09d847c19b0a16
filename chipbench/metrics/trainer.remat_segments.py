"""Segments of the step's graph lowered under ``jax.checkpoint``, by the
program's counter ``mxtpu_remat_segments_total``: each is a part of the
forward pass that the backward pass computes a second time. The counter
moves when a step is TRACED and one process runs one cell, so it reads the
capture's segments; twice that says the step was traced twice. None for a
program without the counter or a graph without a segment."""


def read(run):
    try:
        from mxnet_tpu.observability import catalog
        return int(catalog.REMAT_SEGMENTS.value()) or None
    except (ImportError, AttributeError):
        return None
