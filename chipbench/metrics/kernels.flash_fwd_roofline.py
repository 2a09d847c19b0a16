"""The forward attention kernel's share of its roofline: the least time the
chip could take for the forward attention FLOPs the step REQUIRES (from the
configuration's shapes, whatever implements them; compute-bound at these
shapes) over the device time per step of the trace's events that are the
Pallas forward kernel (custom calls named ``flash_attention_fwd``: the
differentiated forward shows as ``jvp_flash_attention_fwd_.N``, a recomputed
call as ``flash_attention_fwd.N``). Calls that the backward pass recomputes
count in the time and not in the FLOPs. None where
the trace holds no such event: a program without the kernel's name, or a
forward that fell back to the plain XLA form."""
from chipbench import flops

KERNEL = "flash_attention_fwd"


def forward_attention_flops_per_item(cfg):
    """2 x multiply-adds of scores and values for one token, over every
    layer of every pass."""
    macs = flops.causal_attention_macs(
        cfg["seq_len"], cfg["num_attention_heads"], cfg["head_dim"],
        cfg["head_dim"])
    return 2 * macs * cfg["num_hidden_layers"] * cfg.get("total_ut_steps", 1)


def read(run):
    t, cfg = run["trace"], run["cfg"]
    if not t or not t["steps"] or "num_attention_heads" not in cfg:
        return None
    fullest = max(t["devices"], key=lambda d: d["busy_ps"])
    kernel_ps = sum(ps for (name, _cat), ps in fullest["by_op"].items()
                    if KERNEL in name)
    if not kernel_ps:
        return None
    least = (forward_attention_flops_per_item(cfg) * run["n_items"]
             / run["chips"] / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / (kernel_ps / 1e12 / t["steps"])
