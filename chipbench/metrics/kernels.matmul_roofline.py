"""The products' share of their roofline: the least time the chip could take
for the FLOPs the step's products REQUIRE (the family's reference: every
convolution and dense layer, attention's scores and values, the held experts;
compute-bound at these shapes; nothing recomputed counts) over the device
time per step of the events that do products, whatever implements them
(``trace.is_product``): XLA's convolution categories, fused epilogues
included, and every Pallas custom call but the bandwidth kernels that
``trace.BANDWIDTH_KERNELS`` names. Each event counts by its self time, so a
``while`` around a scan's products counts them once."""


def read(run):
    t = run["trace"]
    if not t or not t["steps"] or not t["matmul_s_per_step"]:
        return None
    least = (run["flops_per_item"] * run["n_items"] / run["chips"]
             / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / t["matmul_s_per_step"]
