"""The grouped expert products' share of their roofline: the least time the
chip could take for the FLOPs the step REQUIRES of its routed experts (6 x
``expert_layer_macs`` x layers x tokens a step, from the configuration's
shapes and this chip's share of the experts, whatever implements them;
compute-bound at these shapes) over the device time per step of the trace's
events that are the Pallas grouped-product kernels (custom calls whose name
holds ``moe_gmm``: the forward's ``moe_gmm_t``, the backward's ``moe_gmm``
for the tokens and ``moe_gmm_dw`` for the weights). Rows of padding, tiles
of an uneven group and calls a backward pass recomputes count in the time
and not in the FLOPs. None where the trace holds no such event: a program
without the kernels, or an expert layer that fell back to the plain form."""
from chipbench import flops

KERNEL = "moe_gmm"


def expert_flops_per_item(cfg):
    """Forward and both backward products of the held experts for one
    token, over every layer."""
    macs = flops.expert_layer_macs(
        3 * flops.dense_macs(cfg["hidden_size"], cfg["moe_intermediate_size"]),
        cfg["num_experts_per_tok"],
        cfg["num_experts"] / cfg["published"]["num_experts"])
    return flops.train_flops(macs * cfg["num_hidden_layers"])


def read(run):
    t, cfg = run["trace"], run["cfg"]
    if not t or not t["steps"] or "moe_intermediate_size" not in cfg:
        return None
    fullest = max(t["devices"], key=lambda d: d["busy_ps"])
    kernel_ps = sum(ps for (name, _cat), ps in fullest["by_op"].items()
                    if KERNEL in name)
    if not kernel_ps:
        return None
    least = (expert_flops_per_item(cfg) * run["n_items"] / run["chips"]
             / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / (kernel_ps / 1e12 / t["steps"])
