"""The convolution/dot kernels' share of their roofline: the least time the
chip could take for the FLOPs the step's convolutions and dense layers require
(compute-bound at these shapes) over the device time per step of the trace
events in XLA's convolution categories, fused epilogues included."""


def read(run):
    t = run["trace"]
    if not t or not t["steps"] or not t["matmul_s_per_step"]:
        return None
    least = (run["flops_per_item"] * run["n_items"] / run["chips"]
             / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / t["matmul_s_per_step"]
