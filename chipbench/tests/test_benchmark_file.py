"""BENCHMARK.json against the contract's static rules, and every entry
resolving to files."""
import json
import os
import re

import pytest

from chipbench import harness

ROOT = harness.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
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


# every name check.numbers can emit: a cell's limits file holds some of them
NUMBERS = ({"loss%d" % n for n in (1, 2, 3)}
           | {"%s_%s_leaf" % (n, w) for n in ("grad1", "dparam", "ddiff")
              for w in ("median", "worst")}
           | {"state1_median_leaf", "sign1_median_leaf"})
TOKENS = harness.load_json(DATA, "tokenbench", "BENCHMARK.json")
CELLS = ([(BENCH, ROOT, w["name"]) for w in BENCH["workloads"]]
         + [(TOKENS, DATA, w["name"]) for w in TOKENS["workloads"]])


@pytest.mark.parametrize("bench,root,workload", CELLS, ids=[c[2] for c in CELLS])
def test_every_cell_resolves_to_files(bench, root, workload):
    """The real cells, and the token rehearsal's: a configuration cut to one
    chip's share passes the same rules."""
    cell, cfg, mix, limits, ref = harness.find_cell(bench, workload, root)
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert cfg["name"] == cell["config"]
    assert os.path.exists(os.path.join(root, entry["file"]))
    # a cut is stated: the same keys as the entry's, each with its published
    # value, and the deployment whose one chip the cell is
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg.get("published", {})) == set(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key], key
    if cfg["reduced"]:
        assert cfg["deployment"]["chips_sharing_a_layer"] >= 1
        assert cfg["deployment"]["how"]
    assert cfg.get("items_per_row", 1) >= 1 and cfg["item"]
    assert mix["pool"] >= mix["followed_steps"]      # followed rows all differ
    assert limits and set(limits) <= NUMBERS
    readers = harness.metric_readers(bench, workload, root)
    assert "step.mfu" in readers and "device.idle_share" in readers
    assert ref.leaf_specs(cfg) and ref.train_flops_per_item(cfg) > 0


def test_the_three_cells_hold_what_they_held():
    for name in ("resnet50_v1.train", "resnet34_v1.train", "resnet50_v1.train_fed"):
        _cell, cfg, _mix, limits, _ref = harness.find_cell(BENCH, name)
        assert limits == {"grad1_median_leaf": 0.05, "dparam_median_leaf": 0.07,
                          "state1_median_leaf": 0.006}
        assert cfg["reduced"] == []


def test_no_harness_line_names_a_configuration_a_family_or_a_model():
    words = {c["name"] for c in BENCH["configs"] + TOKENS["configs"]}
    words |= {"resnet", "vgg", "gated_mlp", "tiny_lm", "kanana", "deepseek"}
    for name in ("run.py", "harness.py", "traffic.py", "follow.py", "check.py"):
        with open(os.path.join(harness.HERE, name)) as f:
            text = f.read().lower()
        assert not [w for w in words if w.lower() in text], name


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
