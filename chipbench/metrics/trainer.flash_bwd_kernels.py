"""Attention calls of the step whose backward pass is the Pallas kernel
``flash_attention_bwd`` and not the blockwise scan, by the program's counter
``mxtpu_flash_attention_bwd_lowered_total{route="pallas"}``: it moves when
an attention backward is TRACED and one process runs one cell, so it reads
the capture's attention calls (16 in ``ouro_2_6b.train``, 4 in
``zaya1_8b.train``); 0 says every backward fell back to the scan. None for a
program without the counter, or one that traced no attention backward."""


def read(run):
    try:
        from mxnet_tpu.observability import catalog
        lowered = catalog.FLASH_ATTENTION_BWD_LOWERED
        pallas = int(lowered.value(route="pallas"))
        return pallas if pallas + int(lowered.value(route="scan")) else None
    except (ImportError, AttributeError):
        return None
