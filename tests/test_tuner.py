"""Autotuner tests (mxnet_tpu/tuner/): search space, roofline + learned
prediction, warm-start cache, the predict->measure->persist loop, and the
best-config -> trainer HLO round trip — all on the CPU backend (the chip
path reuses exactly this code through tools/mxtune.py)."""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, tuner
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import catalog, xcost
from mxnet_tpu.tuner import (Candidate, LinearCorrection, SearchSpace,
                             VariantSpec, parse_variants, roofline_ms)

pytestmark = pytest.mark.tuner


# ---------------------------------------------------------------- harness
def _peaks(monkeypatch, flops="1e12", gbps="1"):
    """The CPU backend is not in the device table: pin synthetic peaks so
    the roofline has a denominator. The tiny-GBps default makes every toy
    net memory-bound, so per-sample byte amortization (weight reuse at
    larger batch) decides the ranking deterministically."""
    monkeypatch.setenv("MXNET_PERF_PEAK_FLOPS", flops)
    monkeypatch.setenv("MXNET_PERF_PEAK_HBM_GBPS", gbps)


_BUILD_SEQ = [0]


def _build(cand):
    """Dense MLP with a fat weight matrix (weights dominate bytes, so
    bigger batches amortize them — the rankable signal). Fresh prefixes
    per call keep global param names collision-free."""
    mx.random.seed(23)
    _BUILD_SEQ[0] += 1
    pfx = "tuner%d_b%d_" % (_BUILD_SEQ[0], cand.batch)
    net = nn.HybridSequential(prefix=pfx)
    net.add(nn.Dense(256, activation="relu", prefix=pfx + "d0_"),
            nn.Dense(4, prefix=pfx + "d1_"))
    net.initialize(mx.init.Xavier())
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def _data(cand):
    rng = np.random.RandomState(0)
    x = rng.randn(cand.batch, 256).astype("float32")
    y = rng.randint(0, 4, (cand.batch,)).astype("float32")
    return x, y


def _ledger(tmp_path):
    return tuner.get_cache(str(tmp_path / "trials.jsonl"))


# ------------------------------------------------------------ search space
def test_candidate_validation_and_roundtrip():
    c = Candidate(512, "NHWC", remat="full", donate=False,
                  prefetch_depth=4)
    assert c.label == "NHWC:512+remat=full+nodonate+pf4"
    assert Candidate.from_dict(c.as_dict()) == c
    assert c.data_shape(224) == (512, 224, 224, 3)
    assert Candidate(8, "NCHW").data_shape(64) == (8, 3, 64, 64)
    # keys are scoped by everything that changes the executable or the
    # wall clock it was measured on — and stable
    assert c.key("TPU v5e", "resnet50") == c.key("TPU v5e", "resnet50")
    assert c.key("TPU v5e", "resnet50") != c.key("cpu", "resnet50")
    assert c.key("TPU v5e", n_devices=8) != c.key("TPU v5e", n_devices=32)
    assert c.key("TPU v5e", compute_dtype="bfloat16") != c.key("TPU v5e")
    assert c.key("TPU v5e", optimizer=("sgd", ())) != \
        c.key("TPU v5e", optimizer=("adam", ()))
    with pytest.raises(TypeError):
        Candidate(256, "NHWC", s2d=True)          # the stem is the op's
    with pytest.raises(MXNetError):
        Candidate(256, "NDHW")
    with pytest.raises(MXNetError):
        Candidate(256, remat="everything")
    with pytest.raises(AttributeError):
        c.batch = 1                               # immutable value object


def test_search_space_enumeration_and_spec():
    sp = SearchSpace(batch=(256, 512), layout=("NCHW", "NHWC"),
                     remat=(None, "full"))
    cands = sp.enumerate()
    assert len(cands) == 8 and len(set(cands)) == 8
    # baseline = first value of every dimension
    assert sp.baseline() == Candidate(256, "NCHW")
    sp2 = SearchSpace.from_spec(
        "batch=8,64;layout=NHWC;remat=none,full;donate=1,0;prefetch=4")
    assert sp2.batch == (8, 64) and sp2.remat == (None, "full")
    assert sp2.donate == (True, False) and sp2.prefetch_depth == (4,)
    for spec in ("bogus=1", "batch=8;s2d=1"):
        with pytest.raises(MXNetError, match="unknown search-space dim"):
            SearchSpace.from_spec(spec)
    with pytest.raises(MXNetError):
        SearchSpace.from_spec("layout=NHWC")      # batch is mandatory


def test_variant_specs_map_to_candidates():
    specs = parse_variants(tuner.SEED_VARIANTS)
    assert [s.variant for s in specs] == \
        ["NCHW:256", "NHWC:512", "RMT:512"]
    assert specs[1].to_candidate() == Candidate(512, "NHWC")
    rmt = specs[2].to_candidate()
    assert rmt.remat == "full" and rmt.layout == "NHWC"
    imp = VariantSpec.parse("IMP:32")
    assert imp.imperative
    with pytest.raises(MXNetError):
        imp.to_candidate()
    for token in ("XYZW:16", "S2D:256"):
        with pytest.raises(MXNetError, match="unknown variant label"):
            VariantSpec.parse(token)


# ------------------------------------------------------- learned correction
def test_linear_correction_needs_two_rows_and_falls_back():
    """<2 measured rows: fit() reports unfitted and predictions are the raw
    roofline floor — the documented clean fallback."""
    corr = LinearCorrection()
    row = {"optimal_ms_compute": 2.0, "optimal_ms_memory": 8.0}
    assert not corr.fit([])
    assert not corr.fit([dict(row, measured_step_ms=16.0)])   # one row
    assert not corr.fitted
    assert corr.predict_ms(row) == roofline_ms(row) == 8.0
    # rows without measurements never count
    assert not corr.fit([row, row])
    assert corr.predict_ms(row) == 8.0


def test_linear_correction_fits_and_corrects():
    """Measured times at 3x the roofline: the fitted correction moves the
    estimate off the optimistic floor (and never below half of it)."""
    corr = LinearCorrection()
    rows = [{"optimal_ms_compute": c, "optimal_ms_memory": m,
             "measured_step_ms": 3.0 * max(c, m)}
            for c, m in ((1.0, 4.0), (2.0, 10.0), (0.5, 2.0))]
    assert corr.fit(rows)
    est = corr.predict_ms({"optimal_ms_compute": 1.5,
                           "optimal_ms_memory": 6.0})
    assert est == pytest.approx(18.0, rel=0.05)
    # a degenerate fit (identical feature rows, contradictory targets that
    # force a non-positive prediction) stays in fallback
    corr2 = LinearCorrection()
    bad = [{"optimal_ms_compute": 1.0, "optimal_ms_memory": 1.0,
            "measured_step_ms": 1e-9},
           {"optimal_ms_compute": 1.0, "optimal_ms_memory": 1.0,
            "measured_step_ms": 1e-9}]
    corr2.fit(bad)
    r = {"optimal_ms_compute": 1.0, "optimal_ms_memory": 4.0}
    assert corr2.predict_ms(r) >= 0.5 * roofline_ms(r)


# -------------------------------------------------------- predict & rank
def test_roofline_prediction_ranks_big_batch_nhwc_first(tmp_path,
                                                        monkeypatch):
    """Satellite acceptance: under a memory-bound roofline the big-batch
    NHWC candidate amortizes the weight bytes and outranks the tiny-batch
    NCHW one — from predictions alone (measure=False), every trial
    persisted as a predicted ledger row."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    cands = [Candidate(8, "NCHW"), Candidate(64, "NHWC")]
    res = tuner.tune(_build, _data, candidates=cands, measure=False,
                     ledger=led, model="ranktest")
    ranked = res.ranked()
    assert [t.candidate.label for t in ranked] == ["NHWC:64", "NCHW:8"]
    assert all(t.provenance == "predicted" for t in ranked)
    assert ranked[0].predicted_img_s > ranked[1].predicted_img_s
    assert res.best.candidate == Candidate(64, "NHWC")
    # every trial persisted: predicted rows keyed by fingerprint + config
    rows = led.rows()
    assert len(rows) == 2
    for r in rows:
        assert r["label"] == tuner.TRIAL_LABEL
        assert r["provenance"] == "predicted"
        assert len(r["fingerprint"]) == 64
        assert r["config_key"] and r["tuner_config"]["batch"] in (8, 64)
        assert r["flops"] > 0 and r["predicted_ms"] > 0


def test_tune_unrankable_without_peaks_raises(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_PERF_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MXNET_PERF_PEAK_HBM_GBPS", raising=False)
    with pytest.raises(MXNetError, match="MXNET_PERF_PEAK"):
        tuner.tune(_build, _data, candidates=[Candidate(8)], measure=False,
                   ledger=_ledger(tmp_path), model="nopeaks")


# --------------------------------------------- measure, cache, warm start
def test_predict_measure_cache_loop_and_warm_start(tmp_path, monkeypatch):
    """THE acceptance loop on the CPU backend: predict -> measure top-K ->
    persist; a repeat search reuses every row (provenance=cached), appends
    nothing, re-lowers nothing, and reproduces the ranking."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    cands = [Candidate(8, "NCHW"), Candidate(64, "NCHW")]
    t0 = catalog.TUNER_TRIALS.value(provenance="predicted") or 0
    res = tuner.tune(_build, _data, candidates=cands, top_k=2, steps=2,
                     warmup=1, ledger=led, model="looptest")
    assert all(t.measured for t in res.trials)
    assert res.best.provenance == "measured"
    assert res.best.throughput and res.best.measured_ms
    assert res.best.mfu and 0 < res.best.mfu < 1
    rows = led.rows()
    # 2 predicted + 2 measured rows, measured ones carrying wall facts
    assert len(rows) == 4
    measured = [r for r in rows if r["provenance"] == "measured"]
    assert len(measured) == 2
    for r in measured:
        assert r["measured_step_ms"] > 0
        assert r["throughput_img_s_per_chip"] > 0
        assert len(r["fingerprint"]) == 64
    assert catalog.TUNER_TRIALS.value(provenance="predicted") == t0 + 2
    assert catalog.TUNER_BEST_MFU.value() == pytest.approx(res.best.mfu)

    # ---- round 2: warm start from the ledger alone
    calls = {"build": 0}
    def counting_build(cand):
        calls["build"] += 1
        return _build(cand)
    res2 = tuner.tune(counting_build, _data, candidates=cands, top_k=2,
                      steps=2, warmup=1, ledger=led, model="looptest")
    assert calls["build"] == 0            # nothing rebuilt or re-lowered
    assert [t.provenance for t in res2.trials] == ["cached", "cached"]
    assert len(led.rows()) == 4           # nothing re-measured/appended
    assert [t.candidate.label for t in res2.ranked()] == \
        [t.candidate.label for t in res.ranked()]
    assert res2.best.candidate == res.best.candidate
    assert res2.best.throughput == pytest.approx(res.best.throughput)


def test_rows_of_an_older_tree_are_misses(tmp_path, monkeypatch):
    """A cache file written while the tuner still had an ``s2d`` dimension:
    every row's ``tuner_config`` and ``config_key`` hold an ``"s2d"`` entry.
    Today's key has none, so such a row is a plain miss (never adopted as a
    measurement of today's candidate), and reading it raises nothing."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    cands = [Candidate(8, "NHWC"), Candidate(64, "NHWC")]
    tuner.tune(_build, _data, candidates=cands, top_k=2, steps=2, warmup=1,
               ledger=led, model="oldtree")
    aged = []
    for row in led.rows():
        s2d = row["tuner_config"]["batch"] == 64   # one False, one True
        row["tuner_config"]["s2d"] = s2d
        row["config_key"] = json.dumps(
            dict(json.loads(row["config_key"]), s2d=s2d), sort_keys=True)
        # that tree's programs are not today's: leave only the key to match
        row["fingerprint"] = "0" * 64
        aged.append(json.dumps(row, sort_keys=True))
    assert len(aged) == 4
    with open(led.path, "w") as f:
        f.write("\n".join(aged) + "\n")

    built = []
    def counting_build(cand):
        built.append(cand)
        return _build(cand)
    res = tuner.tune(counting_build, _data, candidates=cands, top_k=2,
                     steps=2, warmup=1, ledger=led, model="oldtree")
    # every candidate lowered anew (and built again to be measured)
    assert built[:2] == cands and len(built) == 4
    assert [t.provenance for t in res.trials] == ["measured", "measured"]
    rows = led.rows()
    assert len(rows) == 8                       # the old rows stay, unread
    assert all("s2d" not in r["tuner_config"] for r in rows[4:])
    # an old row still reads back as a candidate of today
    assert Candidate.from_dict(rows[0]["tuner_config"]) in cands


def test_fingerprint_level_warm_start_skips_remeasure(tmp_path,
                                                     monkeypatch):
    """Two configs that lower to the SAME executable (Dense nets ignore
    layout) share a fingerprint: the second measure slot reuses the first
    one's measurement instead of paying for the trial again."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)

    def build_fixed(cand):
        mx.random.seed(23)
        pfx = "tunfp_b%d_" % cand.batch    # layout-independent prefix:
        net = nn.HybridSequential(prefix=pfx)   # NHWC/NCHW lower identical
        net.add(nn.Dense(32, prefix=pfx + "d0_"))
        net.initialize(mx.init.Xavier())
        return net, gluon.loss.L2Loss()

    def data_fixed(cand):
        rng = np.random.RandomState(0)
        return (rng.randn(cand.batch, 16).astype("float32"),
                rng.randn(cand.batch, 32).astype("float32"))

    cands = [Candidate(16, "NCHW"), Candidate(16, "NHWC")]
    res = tuner.tune(build_fixed, data_fixed, candidates=cands, top_k=2,
                     steps=2, warmup=1, ledger=led, model="fptest")
    provs = sorted(t.provenance for t in res.trials)
    assert provs == ["cached", "measured"]
    cached = next(t for t in res.trials if t.provenance == "cached")
    measured = next(t for t in res.trials if t.provenance == "measured")
    assert cached.fingerprint == measured.fingerprint
    assert cached.measured_ms == pytest.approx(measured.measured_ms)
    # the adopting trial's row carries the measured facts under its OWN
    # config identity (what --emit-best hands perfwatch as a baseline)
    assert cached.cost_row["measured_step_ms"] == pytest.approx(
        measured.measured_ms)
    assert cached.cost_row["tuner_config"] == cached.candidate.as_dict()
    # exactly ONE measured row hit the ledger
    assert sum(1 for r in led.rows()
               if r["provenance"] == "measured") == 1


def test_fingerprint_adoption_is_device_scoped(tmp_path, monkeypatch):
    """A measured row with the SAME fingerprint but another device kind
    must never donate its wall clock: the trial is measured for real
    (a StableHLO digest carries no device identity)."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    cand = Candidate(16, "NCHW")
    # phase 1: predict-only, so the real fingerprint lands in the ledger
    res = tuner.tune(_build, _data, candidates=[cand], measure=False,
                     ledger=led, model="devscope")
    fp = res.trials[0].fingerprint
    # poison: same fingerprint, measured on a different chip/topology
    led.append({"label": tuner.TRIAL_LABEL, "provenance": "measured",
                "fingerprint": fp, "device_kind": "TPU v99",
                "n_devices": 4096, "model": "devscope",
                "measured_step_ms": 1e-6,
                "throughput_img_s_per_chip": 9e12,
                "config_key": "foreign"})
    res2 = tuner.tune(_build, _data, candidates=[cand], top_k=1, steps=2,
                      warmup=1, ledger=led, model="devscope")
    t = res2.trials[0]
    assert t.provenance == "measured"          # NOT adopted from v99
    assert t.throughput < 9e12


def test_feed_mode_measures_through_prefetch_and_scopes_cache(
        tmp_path, monkeypatch):
    """feed=True times trials through io.prefetch_to_device at the
    candidate's depth; its rows are keyed separately from device-resident
    ones (wall clocks are not comparable) and prefetch-differing
    candidates are not collapsed by fingerprint adoption."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)

    def build_fixed(cand):
        # deterministic prefix: both depths lower to the SAME executable
        mx.random.seed(23)
        pfx = "tunfeed_b%d_" % cand.batch
        net = nn.HybridSequential(prefix=pfx)
        net.add(nn.Dense(32, prefix=pfx + "d0_"))
        net.initialize(mx.init.Xavier())
        return net, gluon.loss.L2Loss()

    def data_fixed(cand):
        rng = np.random.RandomState(0)
        return (rng.randn(cand.batch, 16).astype("float32"),
                rng.randn(cand.batch, 32).astype("float32"))

    cands = [Candidate(16, "NCHW", prefetch_depth=1),
             Candidate(16, "NCHW", prefetch_depth=3)]
    res = tuner.tune(build_fixed, data_fixed, candidates=cands, top_k=2,
                     steps=2, warmup=1, ledger=led, model="feedtest",
                     feed=True)
    # same executable, but BOTH measured: depth is a feed-level knob the
    # fingerprint cannot see, so adoption is refused in feed mode
    assert [t.provenance for t in res.trials] == ["measured", "measured"]
    assert res.trials[0].fingerprint == res.trials[1].fingerprint
    rows = [r for r in led.rows() if r.get("measured_step_ms")]
    assert len(rows) == 2 and all(r["feed"] is True for r in rows)
    # a device-resident search over the same configs shares nothing:
    # neither config-key (feed flag in the key) nor fingerprint adoption
    # (feed-mode donor rows) may hand feed wall clocks to resident trials
    res2 = tuner.tune(build_fixed, data_fixed, candidates=cands, top_k=2,
                      steps=2, warmup=1, ledger=led, model="feedtest",
                      feed=False)
    assert "cached" not in {t.provenance for t in res2.trials[:1]}


def test_data_shape_is_part_of_the_cache_key(tmp_path, monkeypatch):
    """The data() callback controls shapes beyond batch/layout: a search
    whose sample batch changes (image size, feature dim) must NOT
    config-key-hit the old rows."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    cand = Candidate(16, "NCHW")
    tuner.tune(_build, _data, candidates=[cand], measure=False,
               ledger=led, model="shapetest")

    def data_wide(c):
        rng = np.random.RandomState(0)
        return (rng.randn(c.batch, 512).astype("float32"),
                rng.randint(0, 4, (c.batch,)).astype("float32"))

    def build_wide(c):
        mx.random.seed(23)
        pfx = "tunwide_b%d_" % c.batch
        net = nn.HybridSequential(prefix=pfx)
        net.add(nn.Dense(256, prefix=pfx + "d0_"),
                nn.Dense(4, prefix=pfx + "d1_"))
        net.initialize(mx.init.Xavier())
        return net, gluon.loss.SoftmaxCrossEntropyLoss()

    res = tuner.tune(build_wide, data_wide, candidates=[cand],
                     measure=False, ledger=led, model="shapetest")
    # fresh prediction, not a stale 256-dim cache hit
    assert res.trials[0].provenance == "predicted"
    assert len(led.rows()) == 2


def test_learned_correction_consumes_measured_rows(tmp_path, monkeypatch):
    """With >=2 measured rows in the cache, a fresh search's predictions
    are corrected off the roofline floor toward wall-clock reality."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    cands = [Candidate(8, "NCHW"), Candidate(64, "NCHW")]
    tuner.tune(_build, _data, candidates=cands, top_k=2, steps=2, warmup=1,
               ledger=led, model="corrtest")
    measured = [r for r in led.rows() if r.get("measured_step_ms")]
    assert len(measured) >= 2
    corr = LinearCorrection()
    assert corr.fit(measured)
    # the corrected estimate is pulled toward measurement: for these CPU
    # toys wall time is far above the roofline floor
    row = measured[0]
    assert corr.predict_ms(row) > roofline_ms(row)


# ------------------------------------------------ best-config round trip
def test_best_config_builds_bitwise_identical_trainer(tmp_path,
                                                      monkeypatch):
    """Acceptance: tune()'s best config applied through the Candidate is
    bitwise the same lowered HLO as building that DataParallelTrainer by
    hand — including a non-default lever (remat)."""
    import jax
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    cands = [Candidate(16, "NCHW"), Candidate(16, "NCHW", remat="full")]
    res = tuner.tune(_build, _data, candidates=cands, measure=False,
                     ledger=led, model="hlotest")
    # round-trip EVERY candidate (the best included), so the check does
    # not depend on which one the cost model happens to rank first
    for trial in res.trials:
        cand = trial.candidate

        def fresh(prefix):
            mx.random.seed(31)
            net = nn.HybridSequential(prefix=prefix)
            net.add(nn.Dense(16, prefix=prefix + "d0_"))
            net.initialize(mx.init.Xavier())
            return net, gluon.loss.L2Loss()

        x = np.random.RandomState(3).randn(16, 8).astype("float32")
        y = np.random.RandomState(4).randn(16, 16).astype("float32")

        def digest(trainer):
            return trainer._lowered_digest(trainer.lower(x, y))

        net_a, loss_a = fresh("rt_%s_a_" % cand.remat)
        via_cand = cand.build_trainer(net_a, loss_a, "sgd",
                                      {"learning_rate": 0.1})
        from mxnet_tpu.parallel import DataParallelTrainer
        net_b, loss_b = fresh("rt_%s_a_" % cand.remat)   # same names
        by_hand = DataParallelTrainer(net_b, loss_b, "sgd",
                                      {"learning_rate": 0.1},
                                      remat=cand.remat, donate=cand.donate)
        assert digest(via_cand) == digest(by_hand)
    # and the result-level applier uses the best candidate
    best = res.best.candidate
    net_c, loss_c = _build(best)
    t = res.build_trainer(net_c, loss_c, "sgd", {"learning_rate": 0.1})
    assert t._remat_mode == best.remat and t._donate == best.donate


def _stem_net(layout):
    """conv -> BatchNorm -> relu -> max pool -> dense: a stem whose pool the
    ``fusion`` pass sinks (PR 30). Fixed names: they are keys of the program."""
    mx.random.seed(29)
    ax = -1 if layout == "NHWC" else 1
    net = nn.HybridSequential(prefix="tstem_")
    net.add(nn.Conv2D(8, 3, 1, 1, use_bias=False, layout=layout,
                      in_channels=3, prefix="tstem_c_"),
            nn.BatchNorm(axis=ax, in_channels=8, prefix="tstem_bn_"),
            nn.Activation("relu"),
            nn.MaxPool2D(3, 2, 1, layout=layout),
            nn.GlobalAvgPool2D(layout=layout),
            nn.Dense(4, in_units=8, prefix="tstem_fc_"))
    net.initialize(mx.init.Xavier())
    return net, gluon.loss.SoftmaxCrossEntropyLoss()


def test_both_routes_of_tune_build_the_default_trainers_step(tmp_path,
                                                             monkeypatch):
    """What ``tune()`` itself builds, on either route and for the NCHW
    baseline too: the step of a default trainer on the net in that layout,
    stem's pool sunk (``mxtpu_pool_sunk_total`` rises once a trial). The
    candidates differ in layout alone, and the two routes' rows are
    interchangeable because their fingerprints are equal."""
    from mxnet_tpu.parallel import DataParallelTrainer
    _peaks(monkeypatch)
    cands = [Candidate(8, "NCHW"), Candidate(8, "NHWC")]

    def data(cand):
        rng = np.random.RandomState(0)
        return (rng.randn(*cand.data_shape(8)).astype("float32"),
                rng.randint(0, 4, (cand.batch,)).astype("float32"))

    prints = {}
    for route, via_passes in (("flags", False), ("passes", True)):
        def build(cand, _flags=not via_passes):
            return _stem_net(cand.layout if _flags else "NCHW")
        before = catalog.POOL_SUNK.value()
        res = tuner.tune(build, data, candidates=cands, measure=False,
                         ledger=_ledger(tmp_path / route), model=route,
                         via_passes=via_passes)
        assert catalog.POOL_SUNK.value() - before == len(cands)
        prints[route] = {t.candidate.layout: t.fingerprint
                         for t in res.trials}
    assert prints["flags"] == prints["passes"]
    assert prints["flags"]["NCHW"] != prints["flags"]["NHWC"]
    # the NHWC candidate is the default trainer on the hand-flagged net
    net, loss_fn = _stem_net("NHWC")
    by_hand = DataParallelTrainer(net, loss_fn, "sgd", {})
    x, y = data(cands[1])
    assert by_hand._lowered_digest(by_hand.lower(x, y)) == \
        prints["flags"]["NHWC"]


# ------------------------------------------------------- cache utilities
def test_best_cached_filters_by_signature(tmp_path, monkeypatch):
    led = _ledger(tmp_path)
    def row(kind, model, tput, batch, net_class="ResNetV1", n_devices=8):
        return {"label": tuner.TRIAL_LABEL, "provenance": "measured",
                "device_kind": kind, "model": model,
                "net_class": net_class, "n_devices": n_devices,
                "measured_step_ms": 1.0,
                "throughput_img_s_per_chip": tput,
                "tuner_config": Candidate(batch).as_dict(),
                "config_key": "k%d" % batch}
    led.append(row("TPU v5e", "resnet50", 2400.0, 256))
    led.append(row("TPU v5e", "resnet50", 3100.0, 512))
    led.append(row("TPU v5e", "tiny", 9e5, 64,
                   net_class="HybridSequential"))
    led.append(row("cpu", "resnet50", 9.0, 8))
    led.append({"label": "bench.resnet50", "device_kind": "TPU v5e",
                "throughput_img_s_per_chip": 9e9})      # not a tuner row
    # model filter (bench's view): a faster tiny-MLP row on the same
    # device must never win a resnet50 query
    best = tuner.best_cached(device_kind="TPU v5e", model="resnet50",
                             ledger=led)
    assert best["throughput_img_s_per_chip"] == 3100.0
    assert best["tuner_config"]["batch"] == 512
    # net_class filter (mxlint's view)
    best = tuner.best_cached(device_kind="TPU v5e",
                             net_class="ResNetV1", ledger=led)
    assert best["tuner_config"]["batch"] == 512
    assert tuner.best_cached(device_kind="TPU v5e",
                             net_class="NoSuchNet", ledger=led) is None
    # n_devices filter: a 32-chip config is no single-chip recommendation
    assert tuner.best_cached(device_kind="TPU v5e", n_devices=8,
                             ledger=led) is not None
    assert tuner.best_cached(device_kind="TPU v5e", n_devices=1,
                             ledger=led) is None
    assert tuner.best_cached(device_kind="TPU v9", ledger=led) is None
    assert tuner.best_cached(device_kind="cpu", ledger=led)[
        "tuner_config"]["batch"] == 8


def test_cache_path_resolution(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TUNER_CACHE", str(tmp_path / "t.jsonl"))
    assert tuner.cache_path() == str(tmp_path / "t.jsonl")
    monkeypatch.delenv("MXNET_TUNER_CACHE")
    monkeypatch.setenv("MXNET_PERF_LEDGER", str(tmp_path / "p.jsonl"))
    assert tuner.cache_path() == str(tmp_path / "p.jsonl")
    monkeypatch.delenv("MXNET_PERF_LEDGER")
    assert tuner.cache_path().endswith("mxtpu_cost_ledger.jsonl")


# ------------------------------------------------ comm search dimensions
def test_candidate_comm_levers():
    """ISSUE 10: grad_reduce / grad_reduce_dtype / bucket_bytes are
    first-class search dimensions — serialized, keyed, validated."""
    c = Candidate(256, grad_reduce="reduce_scatter",
                  grad_reduce_dtype="bf16")
    assert c.label == "NCHW:256+rs+rd=bfloat16"
    assert c.grad_reduce_dtype == "bfloat16"          # normalized spelling
    assert Candidate.from_dict(c.as_dict()) == c
    b = Candidate(256, bucket_bytes=1 << 20)
    assert b.label == "NCHW:256+bb=%d" % (1 << 20)
    # the comm config is part of the warm-start identity: a reduce_scatter
    # measurement must never warm-start an all_reduce search
    base_key = Candidate(256).key("cpu")
    assert c.key("cpu") != base_key
    assert b.key("cpu") != base_key
    assert Candidate(256, grad_reduce_dtype="bfloat16").key("cpu") != \
        base_key
    with pytest.raises(MXNetError):
        Candidate(256, grad_reduce="ring")
    with pytest.raises(MXNetError):
        Candidate(256, grad_reduce_dtype="float64")
    with pytest.raises(MXNetError):
        Candidate(256, grad_reduce="reduce_scatter", bucket_bytes=1024)


def test_search_space_comm_dims_enumeration():
    sp = SearchSpace.from_spec(
        "batch=32;layout=NCHW;grad_reduce=all_reduce,reduce_scatter;"
        "grad_reduce_dtype=none,bf16;bucket_bytes=none,65536")
    cands = sp.enumerate()
    # 2 x 2 x 2 = 8 minus the 2 invalid reduce_scatter+bucket combos
    assert len(cands) == 6
    assert sp.baseline() == Candidate(32)             # first-of-every-dim
    assert any(c.grad_reduce == "reduce_scatter"
               and c.grad_reduce_dtype == "bfloat16" for c in cands)
    assert any(c.bucket_bytes == 65536 for c in cands)
    assert all(not (c.bucket_bytes and c.grad_reduce == "reduce_scatter")
               for c in cands)
    # alias spellings parse too
    sp2 = SearchSpace.from_spec("batch=8;reduce=reduce_scatter;bucket=none")
    assert sp2.enumerate()[0].grad_reduce == "reduce_scatter"


def test_comm_candidate_builds_bitwise_identical_trainer():
    """A comm-lever candidate applied through build_trainer lowers to the
    SAME StableHLO as hand-written DataParallelTrainer kwargs — the tuner
    measures exactly the program the user would run."""
    from mxnet_tpu.parallel import DataParallelTrainer
    cand = Candidate(16, grad_reduce="reduce_scatter",
                     grad_reduce_dtype="bf16")

    def fresh():
        mx.random.seed(31)
        net = nn.HybridSequential(prefix="commrt_")
        net.add(nn.Dense(16, prefix="commrt_d0_"))
        net.initialize(mx.init.Xavier())
        return net, gluon.loss.L2Loss()

    x = np.random.RandomState(3).randn(16, 8).astype("float32")
    y = np.random.RandomState(4).randn(16, 16).astype("float32")
    net_a, loss_a = fresh()
    via_cand = cand.build_trainer(net_a, loss_a, "sgd",
                                  {"learning_rate": 0.1})
    net_b, loss_b = fresh()
    by_hand = DataParallelTrainer(net_b, loss_b, "sgd",
                                  {"learning_rate": 0.1}, passes=False,
                                  grad_reduce="reduce_scatter",
                                  grad_reduce_dtype="bf16")
    assert via_cand._lowered_digest(via_cand.lower(x, y)) == \
        by_hand._lowered_digest(by_hand.lower(x, y))
    # and the lever actually reached the trainer
    assert via_cand.comm_config()["grad_reduce"] == "reduce_scatter"
    assert via_cand.comm_config()["grad_reduce_dtype"] == "bfloat16"


def test_tune_searches_comm_space(tmp_path, monkeypatch):
    """mxtune-style search over {grad_reduce, grad_reduce_dtype,
    bucket_bytes}: every trial lands in the cache with its comm config in
    tuner_config, and a repeat search is a pure warm start."""
    _peaks(monkeypatch)
    led = _ledger(tmp_path)
    sp = SearchSpace(batch=(16,), layout=("NCHW",),
                     grad_reduce=("all_reduce", "reduce_scatter"),
                     grad_reduce_dtype=(None, "bf16"))
    res = tuner.tune(_build, _data, sp, measure=True, top_k=1, steps=2,
                     warmup=0, ledger=led, model="commsearch")
    assert len(res.trials) == 4
    rows = [r for r in led.rows() if r.get("label") == tuner.TRIAL_LABEL]
    configs = {(r["tuner_config"]["grad_reduce"],
                r["tuner_config"]["grad_reduce_dtype"]) for r in rows}
    assert configs == {("all_reduce", None), ("all_reduce", "bfloat16"),
                       ("reduce_scatter", None),
                       ("reduce_scatter", "bfloat16")}
    assert any(r.get("measured_step_ms") for r in rows)
    # warm start: the repeat search reuses every row, appends only the
    # next measured trial's facts (config-key hits re-lower nothing)
    n_before = len(led.rows())
    res2 = tuner.tune(_build, _data, sp, measure=False, ledger=led,
                      model="commsearch")
    assert all(t.provenance == "cached" for t in res2.trials
               if t.error is None)
    assert len(led.rows()) == n_before
