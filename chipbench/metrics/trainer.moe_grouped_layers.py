"""Expert layers of the step whose products are grouped products over tokens
sorted by expert (the Pallas kernels ``moe_gmm*``), by the program's counter
``mxtpu_moe_lowered_total{route="grouped"}``: it moves when an expert
layer's gradient is TRACED and one process runs one cell, so it reads the
capture's expert layers (4 in ``zaya1_8b.train``); 0 says every expert layer
fell back to the plain per-expert form, which runs each held expert over all
tokens. None for a program without the counter, or one that traced no expert
layer at all."""


def read(run):
    try:
        from mxnet_tpu.observability import catalog
        lowered = catalog.MOE_LOWERED
        grouped = int(lowered.value(route="grouped"))
        return grouped if grouped + int(lowered.value(route="plain")) else None
    except (ImportError, AttributeError):
        return None
