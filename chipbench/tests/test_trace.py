"""The trace reduction against a small trace recorded on a v5e chip
(data/step3.xplane.pb: three runs of a tiny conv+dense step, ``jit_step``,
2 ms of host sleep between them) and its interval arithmetic."""
import os

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_recorded_trace_busy_idle_and_categories():
    planes = trace.read(os.path.join(DATA, "step3.xplane.pb"))
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    r = trace.reduce(planes, step_module="jit_step", spans=("trainer.step",))
    assert r["n_devices"] == 1 and r["steps"] == 3
    d = r["devices"][0]
    # the union never exceeds the sum of the op times, nor the modules' time
    assert 0 < d["busy_ps"] <= sum(d["by_cat"].values())
    assert d["busy_ps"] <= sum(d["step_ps"]) * 1.001
    assert abs(r["busy_s"] - 2.1174766e-05) < 1e-9
    assert set(d["by_cat"]) >= {"convolution fusion", "loop fusion", "data formatting"}
    conv = d["by_cat"]["convolution fusion"]
    assert abs(r["matmul_s_per_step"] - conv / 3 / 1e12) < 1e-15
    assert abs(r["matmul_s_per_step"] + r["other_s_per_step"]
               - sum(d["by_cat"].values()) / 3 / 1e12) < 1e-15
    assert r["collective_s_per_step"] == 0 and r["exposed_collective_s_per_step"] == 0
    # the two long gaps are the sleeps, when no harness span was open
    gaps = r["idle_gaps"]
    assert gaps[0][1] > 3e-3 and gaps[1][1] > 3e-3 and gaps[2][1] < 1e-4
    assert gaps[0][0] == "no span open"
    assert r["device_ops"][0][0].endswith("[convolution fusion]")


def test_union_and_exposed_collective_time():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.total(trace.union([(0, 10), (2, 3)])) == 10
    # an all-reduce from 10 to 30; compute covers 0-15 and 25-28: exposed 15-25, 28-30
    exposed = trace.subtract([(10, 30)], trace.union([(0, 15), (25, 28)]))
    assert exposed == [(15, 25), (28, 30)] and trace.total(exposed) == 12
    assert trace.subtract([(0, 4)], []) == [(0, 4)]
    assert trace.subtract([(0, 4)], [(0, 4)]) == []


def test_collectives_are_told_from_compute():
    meta = {1: ("%fusion.1 = bf16[8] fusion(...)", "convolution fusion"),
            2: ("%all-reduce.3 = f32[8] all-reduce(...)", "all-reduce"),
            3: ("jit_train_step(1)", None)}
    plane = {"name": "/device:TPU:0", "meta": meta, "lines": {
        "XLA Modules": [(0, 100, 3)],
        "XLA Ops": [(0, 40, 1), (40, 30, 2), (80, 20, 1)],
        "Async XLA Ops": [(30, 40, 2)]}}
    r = trace.reduce([plane])
    assert r["steps"] == 1
    assert r["collective_s_per_step"] == 40e-12       # 30..70
    assert r["exposed_collective_s_per_step"] == 30e-12  # 40..70; 30..40 is hidden
    assert r["matmul_s_per_step"] == 60e-12
    assert r["busy_s"] == 90e-12
