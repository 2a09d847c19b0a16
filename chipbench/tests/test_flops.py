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
