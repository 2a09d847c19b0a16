"""BENCHMARK.json against the contract's static rules, and every entry
resolving to files."""
import json
import os
import re

import pytest

from chipbench import harness

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_are_exactly_the_contracts():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds", "configs",
                                    "workloads", "end_to_end", "per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, cells // 4)
    # a full check with the full 24 cells has to fit the driver's budget
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_files(workload):
    cell, cfg, mix, limits, ref = harness.find_cell(BENCH, workload)
    assert cfg["name"] == cell["config"] and cfg["reduced"] == []
    assert mix["pool"] >= mix["followed_steps"]      # followed rows all differ
    assert set(limits) == {"grad1_median_leaf", "dparam_median_leaf", "state1_median_leaf"}
    readers = harness.metric_readers(BENCH, workload)
    assert "step.mfu" in readers and "device.idle_share" in readers
    assert ref.leaf_specs(cfg)
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_files_under_paths_keep_to_the_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, _dirs, files in os.walk(os.path.join(ROOT, "chipbench")):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel), rel


def test_peaks_have_their_source():
    peaks = harness.load_json(harness.HERE, "peaks.json")
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "source" in row
