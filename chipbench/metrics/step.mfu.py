"""The whole step's share of the chip's bf16 peak: FLOPs the model requires
per item (chipbench/flops.py, from shapes) x items/s/chip over the peak of
chipbench/peaks.json. Nothing recomputed counts."""


def read(run):
    w = run["window"]
    if not w["steps"]:
        return None
    rate = w["steps"] * run["n_items"] / w["seconds"] / run["chips"]
    return 100.0 * run["flops_per_item"] * rate / run["peaks"]["bf16_flops_per_s"]
