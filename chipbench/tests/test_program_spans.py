"""The program's own spans read by the benchmark (data/tinybench/
BENCHMARK.spans.json: the tiny bench plus the six ``program_span`` metrics): a
traced rehearsal prints all six, the helper's window is the harness's window
step for step, and with telemetry off the readers find nothing and the line
leaves the metrics out."""
import math
import os

import pytest

from chipbench import harness, program_spans, run
from chipbench.tests.test_run import DATA, fake_chip

BENCH = os.path.join(DATA, "tinybench", "BENCHMARK.spans.json")
SIX = {"trainer.enqueue_ms", "trainer.host_ms", "feed.starved_ms",
       "feed.produce_ms", "trainer.capture_s", "jit.compile_s"}
FEED = {"feed.starved_ms", "feed.produce_ms"}


def rehearse(workload, seed=2147484007):
    return run.run(["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
                    "--trace", "1"], bench_path=BENCH, root=DATA, require_chip=fake_chip)


def test_the_second_bench_file_adds_the_six_and_nothing_else():
    first = harness.load_json(DATA, "tinybench", "BENCHMARK.json")
    second = harness.load_json(BENCH)
    n = len(first["per_layer"])
    assert second["per_layer"][:n] == first["per_layer"]
    assert {m["name"] for m in second["per_layer"][n:]} == SIX
    assert all(m["source"] == "program_span" for m in second["per_layer"][n:])
    assert {k: v for k, v in second.items() if k != "per_layer"} \
        == {k: v for k, v in first.items() if k != "per_layer"}
    # and the real file lists them under the same names
    real = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert SIX <= {m["name"] for m in real["per_layer"]}


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.train_fed"])
def test_a_traced_rehearsal_prints_the_six_and_the_window_is_the_harness_s(
        workload, monkeypatch):
    seen = {}
    select = program_spans.select

    def spy(run_):
        seen["run"] = run_
        return select(run_)

    monkeypatch.setattr(program_spans, "select", spy)
    r = rehearse(workload)
    fed = workload.endswith("fed")
    assert r["correct"] and r["metrics"]["trainer.compiles_in_window"]["value"] == 0
    assert SIX & set(r["metrics"]) == (SIX if fed else SIX - FEED)
    for name in SIX & set(r["metrics"]):
        v = r["metrics"][name]["value"]
        assert math.isfinite(v) and v >= 0, (name, v)
    assert r["metrics"]["trainer.capture_s"]["value"] > 0
    assert r["metrics"]["jit.compile_s"]["value"] > 0

    w = select(seen["run"])
    outer = seen["run"]["spans"]["trainer.step"]
    assert len(w["steps"]) == len(outer) == r["attempted"]
    # the program's root lies inside the harness span of the same index and
    # within 0.2 ms of it (here, with the watcher and XLA's CPU threads on the
    # same few cores, a thread switch falls between the two clock reads now
    # and then: nine steps in ten hold it, and the median by far)
    over = sorted(h - st["trainer.step"] for st, h in zip(w["steps"], outer))
    assert over[0] >= 0 and over[len(over) // 2] < 1e-4
    assert over[int(0.9 * (len(over) - 1))] < 2e-4, over
    for st in w["steps"]:
        assert {"trainer.put", "trainer.rng", "trainer.enqueue"} <= set(st)
        assert "trainer.capture" not in st          # no re-capture in the window
        assert st["trainer.put"] + st["trainer.rng"] + st["trainer.enqueue"] \
            <= st["trainer.step"]
    if fed:
        waits = seen["run"]["spans"]["feed.next"]
        assert len(w["batches"]) == len(waits)
        for b, h in zip(w["batches"], waits):
            assert b["feed.get_wait"] <= h
            assert b["feed.base_next"] > 0 and b["feed.stage"] > 0
    else:
        assert w["batches"] == []


def test_with_telemetry_off_the_line_leaves_them_out(monkeypatch):
    import collections
    from mxnet_tpu.observability import spans
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    # a ring of its own: the rehearsals before left their records in the process's
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=64))
    r = rehearse("tiny.train_fed")
    assert r["correct"] and not SIX & set(r["metrics"])
    assert "step.mfu" in r["metrics"]                 # the harness's own still read
    assert spans.records() == []


def test_a_program_without_the_record_reads_none(monkeypatch):
    """The parent of the PR that brought the record runs these readers too."""
    from mxnet_tpu.observability import spans
    monkeypatch.delattr(spans, "records")
    fake = {"mix": {"followed_steps": 3, "warmup_steps": 1},
            "spans": {"trainer.step": [0.001] * 5}}
    assert program_spans.select(fake) == program_spans.EMPTY
    for name, read in harness.metric_readers(
            harness.load_json(BENCH), "tiny.train_fed", DATA).items():
        if name in SIX:
            assert read(fake) is None
