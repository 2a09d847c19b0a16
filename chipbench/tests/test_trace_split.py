"""How ``trace.reduce`` splits a step's device time into the products' time
(``matmul_s_per_step``) and the other time (``other_s_per_step``), on
synthetic device planes in the shape ``trace._plane`` returns: every event
counts once (an enclosing ``while`` by its self time), and a product counts
as product time whatever implements it, an XLA convolution fusion or a
Pallas custom call (PR 35)."""
import os
import random

import pytest

from chipbench import harness, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PEAKS = harness.load_json(ROOT, "chipbench", "peaks.json")["TPU v5 lite"]


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "chipbench", "metrics", name + ".py"),
        "split_" + name.replace(".", "_")).read


def _plane(events, steps=1):
    """One device plane: ``events`` are (name, category, start, duration),
    a custom call's with its target as a fifth item; ``steps`` runs of the
    step module cover them all."""
    meta, ops = {0: ("jit_train_step(1)", None)}, []
    for i, (nm, cat, s, d, *target) in enumerate(events, 1):
        meta[i] = ("%%%s = bf16[8] op(...)%s" % (nm, "".join(
            ', custom_call_target="%s"' % t for t in target)), cat)
        ops.append((s, d, i))
    end = max(e[2] + e[3] for e in events)
    mods = [(k * end // steps, end // steps, 0) for k in range(steps)]
    return {"name": "/device:TPU:0", "meta": meta,
            "lines": {"XLA Modules": mods, "XLA Ops": ops}}


def _run(reduced, cfg=None):
    return {"trace": reduced, "cfg": cfg or {}, "chips": 1, "n_items": 1000,
            "flops_per_item": 1e9, "peaks": PEAKS}


# -------------------------------------------- (a) a while counts once
def test_a_while_counts_its_self_time_and_each_body_op_once():
    r = trace.reduce([_plane([
        ("while.7", "while", 0, 100),
        ("fusion.1", "convolution fusion", 5, 50),
        ("add_fusion.2", "loop fusion", 60, 30),
        ("copy.3", "data formatting", 100, 20)])])
    d = r["devices"][0]
    assert d["busy_ps"] == 120
    assert d["by_cat"] == {"while": 20, "convolution fusion": 50,
                           "loop fusion": 30, "data formatting": 20}
    assert sum(d["by_cat"].values()) <= d["busy_ps"]
    assert r["matmul_s_per_step"] == 50e-12
    assert r["other_s_per_step"] == 70e-12
    # the breakdown names the ops inside the loop before its shell
    assert [n for n, _v in r["device_ops"]] == [
        "fusion.1 [convolution fusion]", "add_fusion.2 [loop fusion]",
        "while.7 [while]", "copy.3 [data formatting]"]


def test_nested_loops_and_equal_intervals_count_once():
    r = trace.reduce([_plane([
        ("while.1", "while", 0, 200),
        ("while.2", "while", 20, 100),     # inside while.1
        ("fusion.3", "convolution fusion", 30, 40),
        ("fusion.4", "loop fusion", 80, 30),
        ("call.5", "call", 150, 40),       # the same interval as its body op
        ("fusion.6", "convolution fusion", 150, 40)])])
    d = r["devices"][0]
    assert d["by_op"][("while.1", "while")] == 200 - 100 - 40
    assert d["by_op"][("while.2", "while")] == 100 - 70
    assert d["by_op"][("call.5", "call")] == 0
    assert d["by_op"][("fusion.6", "convolution fusion")] == 40
    assert sum(d["by_cat"].values()) == d["busy_ps"] == 200
    assert r["matmul_s_per_step"] == 80e-12


def test_partial_overlaps_and_other_lines_are_counted_whole():
    plane = _plane([("fusion.1", "loop fusion", 0, 60),
                    ("fusion.2", "loop fusion", 40, 60)])
    plane["lines"]["Async XLA Ops"] = [(0, 100, 1)]   # not nesting: another line
    d = trace.reduce([plane])["devices"][0]
    assert d["by_cat"] == {"loop fusion": 120} and d["busy_ps"] == 100


@pytest.mark.parametrize("seed", range(8))
def test_the_categories_never_sum_past_the_busy_time(seed):
    """Random nests of loops, each holding a sequence of ops and loops."""
    rng, events = random.Random(seed), []
    cats = ("convolution fusion", "loop fusion", "custom-call", "copy")

    def fill(start, end, depth):
        t = start
        while t < end:
            d = rng.randint(1, max(1, (end - t) // 2))
            if depth < 3 and rng.random() < 0.3:
                events.append(("while.%d" % len(events), "while", t, d))
                fill(t + rng.randint(0, d // 4), t + d, depth + 1)
            else:
                events.append(("op.%d" % len(events), rng.choice(cats), t, d))
            t += d + rng.randint(0, 3)

    fill(0, 10_000, 0)
    r = trace.reduce([_plane(events, steps=2)])
    d = r["devices"][0]
    assert sum(d["by_cat"].values()) == d["busy_ps"]
    assert min(d["by_op"].values()) >= 0
    assert abs((r["matmul_s_per_step"] + r["other_s_per_step"]) * 2e12
               - d["busy_ps"]) < 1e-3


# ------------------------ (b) the products whatever implements them
STEP = [("fusion.1", 0, 40), ("fusion.2", 50, 30), ("fusion.3", 90, 20),
        ("add_fusion.4", 120, 10)]


def _step(kernels):
    """STEP with its first three products as convolution fusions, or as the
    Pallas calls ``kernels`` names; the loop fusion stays."""
    out = []
    for i, (nm, s, d) in enumerate(STEP):
        if nm.startswith("add"):
            out.append((nm, "loop fusion", s, d))
        elif kernels:
            out.append((kernels[i], "custom-call", s, d, "tpu_custom_call"))
        else:
            out.append((nm, "convolution fusion", s, d))
    return out


def test_products_read_the_same_as_fusions_or_as_pallas_calls():
    xla = trace.reduce([_plane(_step(None))])
    pallas = trace.reduce([_plane(_step(
        ["flash_attention_bwd.3", "moe_gmm_dw.1", "jvp_flash_attention_fwd_.2"]))])
    assert xla["matmul_s_per_step"] == pallas["matmul_s_per_step"] == 90e-12
    assert xla["other_s_per_step"] == pallas["other_s_per_step"] == 10e-12
    roofline = _reader("kernels.matmul_roofline")
    assert roofline(_run(xla)) == roofline(_run(pallas))
    assert roofline(_run(xla)) == pytest.approx(
        100 * 1e12 / 197e12 / 90e-12)


def test_a_scan_moved_into_a_kernel_leaves_the_roofline_where_it_was():
    """PR 34's change: the attention backward's products inside a ``while``
    (a ``lax.scan``) become one Pallas call over the same interval."""
    scan = trace.reduce([_plane([
        ("while.76", "while", 0, 100),
        ("convolution_add_fusion.31", "convolution fusion", 5, 60),
        ("bitcast_dynamic-update-slice_fusion", "loop fusion", 70, 25),
        ("fusion.9", "convolution fusion", 100, 50)])])
    kernel = trace.reduce([_plane([
        ("flash_attention_bwd.4", "custom-call", 0, 100, "tpu_custom_call"),
        ("fusion.9", "convolution fusion", 100, 50)])])
    # the loop's elementwise work and its shell are other time; the kernel's
    # whole interval is products
    assert scan["matmul_s_per_step"] == 110e-12
    assert scan["other_s_per_step"] == 40e-12
    assert kernel["matmul_s_per_step"] == 150e-12
    assert kernel["other_s_per_step"] == 0
    for r in (scan, kernel):
        assert (r["matmul_s_per_step"] + r["other_s_per_step"]) * 1e12 \
            <= r["devices"][0]["busy_ps"] * 1.001


# --------------------------------- (c) the bandwidth kernels are other time
@pytest.mark.parametrize("name,target", [
    ("max_pool_fwd.1", "tpu_custom_call"), ("max_pool_bwd.1", "tpu_custom_call"),
    ("cross_entropy_lse.2", "tpu_custom_call"),
    ("jvp_cross_entropy_lse_.3", "tpu_custom_call"),
    ("custom-call", "X64SplitHigh"), ("custom-call.2", "X64Combine")])  # XLA's own
def test_bandwidth_kernels_and_xla_s_own_calls_stay_in_the_other_time(name, target):
    r = trace.reduce([_plane([("fusion.1", "convolution fusion", 0, 40),
                              (name, "custom-call", 40, 20, target)])])
    assert r["matmul_s_per_step"] == 40e-12
    assert r["other_s_per_step"] == 20e-12


def test_a_custom_call_is_a_kernel_unless_it_names_another_target():
    text = '%moe_gmm_t.7 = bf16[8] custom-call(...), custom_call_target="{}"'
    assert trace.is_product(text.format("tpu_custom_call"), "custom-call")
    assert trace.is_product("%moe_gmm_t.7 = bf16[8] custom-call(...)", "custom-call")
    assert not trace.is_product(text.format("X64SplitLow"), "custom-call")
    assert not trace.is_product("%max_pool_fwd.1 = bf16[8] fusion(...)", "loop fusion")


# ------------ (d) the kernels' own rooflines read by_op of custom calls
def _cfg(name):
    return harness.load_json(ROOT, "chipbench", "configs", name + ".json")


@pytest.mark.parametrize("metric,kernel,config", [
    ("kernels.flash_fwd_roofline", "jvp_flash_attention_fwd_.5", "ouro_2_6b"),
    ("kernels.moe_gmm_roofline", "moe_gmm_dw.1", "zaya1_8b")])
def test_kernel_rooflines_read_the_same_inside_a_loop(metric, kernel, config):
    flat = [(kernel, "custom-call", 10, 4_000_000, "tpu_custom_call"),
            ("fusion.1", "convolution fusion", 4_000_010, 1_000_000)]
    looped = [("while.1", "while", 0, 5_000_020)] + flat
    read, cfg = _reader(metric), _cfg(config)
    values = [read(_run(trace.reduce([_plane(ev)]), cfg)) for ev in (flat, looped)]
    assert values[0] is not None and values[0] == values[1]
    r = trace.reduce([_plane(looped)])
    assert r["devices"][0]["by_op"][(kernel, "custom-call")] == 4_000_000
