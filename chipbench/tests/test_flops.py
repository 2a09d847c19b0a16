"""flops.py against hand counts."""
from chipbench import flops, harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")


def layers(workload):
    _cell, cfg, _mix, _limits, ref = harness.find_cell(BENCH, workload)
    return ref.conv_layers(cfg)


def test_resnet50_bottleneck_block_layer_by_layer():
    ls = layers("resnet50_v1.train")
    # stem: 7x7x3 -> 64 at 112x112
    assert flops.layer_macs(ls[0]) == 49 * 3 * 64 * 112 * 112 == 118013952
    # stage 1, block 1 at 56x56: 1x1 64->64, 3x3 64->64, 1x1 64->256, and the
    # 1x1 64->256 projection of the shortcut
    assert [flops.layer_macs(l) for l in ls[1:5]] == [
        64 * 64 * 3136, 9 * 64 * 64 * 3136, 64 * 256 * 3136, 64 * 256 * 3136]
    # stage 2, block 1: the zoo's v1 strides on the FIRST 1x1, so all three
    # convolutions run at 28x28
    first = [l for l in ls if l["cin"] == 256 and l["cout"] == 128][0]
    assert first["out_hw"] == 28 and first["k"] == 1
    assert len(ls) == 1 + 16 * 3 + 4 + 1
    total = flops.forward_macs(ls)
    assert 3.8e9 < total < 3.9e9, total          # stride-on-1x1 variant
    assert flops.train_flops_per_item(ls) == 6 * total


def test_resnet34_total():
    ls = layers("resnet34_v1.train")
    assert len(ls) == 1 + 16 * 2 + 3 + 1
    assert 3.55e9 < flops.forward_macs(ls) < 3.7e9


def test_vgg16_by_hand_is_what_the_issue_says():
    """VGG-16 (configuration D) is not a cell (PERF.md Open questions), but
    the counting rule is checked on it: 15.47 G multiply-adds."""
    ls, hw, cin = [], 224, 3
    for n, c in zip([2, 2, 3, 3, 3], [64, 128, 256, 512, 512]):
        for _ in range(n):
            ls.append(dict(kind="conv", k=3, cin=cin, cout=c, out_hw=hw))
            cin = c
        hw //= 2
    for cin, cout in ((512 * 49, 4096), (4096, 4096), (4096, 1000)):
        ls.append(dict(kind="dense", k=1, cin=cin, cout=cout, out_hw=1))
    assert abs(flops.forward_macs(ls) / 1e9 - 15.47) < 0.01
    assert abs(flops.train_flops_per_item(ls) / 1e9 - 92.8) < 0.1


def test_the_run_asks_the_reference_and_gets_the_same_digits():
    for workload, gflop in (("resnet50_v1.train", 23.15), ("resnet34_v1.train", 21.98)):
        _cell, cfg, _mix, _limits, ref = harness.find_cell(BENCH, workload)
        assert ref.train_flops_per_item(cfg) == flops.train_flops_per_item(
            ref.conv_layers(cfg))
        assert round(ref.train_flops_per_item(cfg) / 1e9, 2) == gflop


def test_per_token_helpers_against_counts_by_hand():
    """A decoder layer of a sparse-expert, latent-attention net at published
    widths (hidden 2,048; 32 heads of 128 + 64 query/key and 128 value dims;
    128 routed experts of width 768, 6 a token, 2 shared; 8 chips share a
    layer, so 16 experts and 16,032 of 128,256 vocabulary rows are held)."""
    # projections: q 2048x6144, kv_a 2048x576, kv_b 512x8192, o 4096x2048
    proj = (flops.dense_macs(2048, 32 * 192) + flops.dense_macs(2048, 512 + 64)
            + flops.dense_macs(512, 32 * 256) + flops.dense_macs(32 * 128, 2048))
    assert proj == 12582912 + 1179648 + 4194304 + 8388608 == 26345472
    # scores at 8,192 packed tokens: position t sees t keys, 4,096.5 on average
    # (half the square), each 192 MACs for the score and 128 for the values,
    # in each of 32 heads
    assert flops.causal_attention_macs(8192, 32, 192, 128) == 32 * 320 * 4096.5 \
        == sum(32 * 320 * t for t in range(1, 8193)) / 8192
    assert flops.causal_attention_macs(1, 32, 192, 128) == 32 * 320     # itself
    # one gated expert: three 2048x768 projections; of a token's 6 experts a
    # chip that holds 16 of 128 sees 0.75 on average, and both shared experts
    expert = 3 * flops.dense_macs(2048, 768)
    assert expert == 4718592
    assert flops.expert_layer_macs(expert, 6, 16 / 128, shared_experts=2) \
        == expert * 2.75 == 12976128
    assert flops.expert_layer_macs(expert, 6, 1.0) == 6 * expert   # all held, none shared
    assert flops.head_macs(2048, 16032) == 32833536
    assert flops.train_flops(proj) == 6 * proj == flops.train_flops_per_item(
        [dict(k=1, cin=proj, cout=1, out_hw=1)])
