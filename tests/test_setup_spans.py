"""Set-up under the program's own spans: ``mxnet_tpu.import``,
``gluon.initialize``, ``gluon.set_data``, ``trainer.init``,
``trainer.sync_to_net`` and ``jit.cache_load`` (records), and the benchmark's
four readers of them (``chipbench/setup_spans.py``, ``chipbench/metrics/
setup.*.py`` and ``jit.compiled_in_setup.py``).

The rehearsals run ``chipbench/run.py``'s ``run`` on the tiny bench in a
process of their own: set-up starts with the process, and the harness turns
the persistent compile cache on for the whole process."""
import collections
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import jit_hooks, spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import harness, setup_spans  # noqa: E402

pytestmark = pytest.mark.obs

FOUR = ("setup.unnamed_s", "setup.import_s", "setup.weights_s",
        "jit.compiled_in_setup")
DATA = os.path.join(REPO, "chipbench", "tests", "data")


@pytest.fixture
def ring(monkeypatch):
    """A ring of the test's own that has dropped nothing."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setattr(spans, "_ring",
                        collections.deque(maxlen=spans.RING_RECORDS))
    monkeypatch.setattr(spans, "_dropped", 0)
    return spans


def _net(prefix):
    net = nn.HybridSequential(prefix=prefix)
    net.add(nn.Dense(8, activation="relu", prefix=prefix + "d0_"),
            nn.Dense(3, prefix=prefix + "d1_"))
    return net


def _trainer(net):
    return parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd", {"learning_rate": 0.1})


def _batch():
    rng = np.random.RandomState(42)
    return rng.randn(16, 6).astype("f4"), rng.randint(0, 3, (16,)).astype("f4")


def _lazy_import(monkeypatch):
    monkeypatch.delitem(vars(mx), "viz", raising=False)   # imported again
    assert mx.viz is mx.visualization


def _initialize(monkeypatch):
    _net("ssi_").initialize(mx.init.Xavier())


def _set_data(monkeypatch):
    net = _net("sss_")
    net.initialize(mx.init.Xavier())          # deferred: shapes unknown
    p = net.collect_params()["sss_d0_weight"]
    p.set_data(mx.nd.ones((8, 6)))
    np.testing.assert_array_equal(p.data().asnumpy(), np.ones((8, 6)))


def _trainer_init(monkeypatch):
    net = _net("sst_")
    net.initialize(mx.init.Xavier())
    _trainer(net)


def _sync_to_net(monkeypatch):
    net = _net("ssy_")
    net.initialize(mx.init.Xavier())
    t = _trainer(net)
    t.step(*_batch())
    before = net.collect_params()["ssy_d1_bias"].data().asnumpy()
    t.sync_to_net()
    assert not np.array_equal(before,
                              net.collect_params()["ssy_d1_bias"].data().asnumpy())


CALLS = {"mxnet_tpu.import": _lazy_import, "gluon.initialize": _initialize,
         "gluon.set_data": _set_data, "trainer.init": _trainer_init,
         "trainer.sync_to_net": _sync_to_net}


@pytest.mark.parametrize("telemetry", ["1", "0"])
@pytest.mark.parametrize("name", sorted(CALLS))
def test_a_setup_span_records_once_a_call_under_its_parent(
        name, telemetry, ring, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", telemetry)
    n0 = len([r for r in spans.records() if r.name == name])
    with spans.span("test.outer"):
        CALLS[name](monkeypatch)
    mine = [r for r in spans.records() if r.name == name][n0:]
    if telemetry == "0":
        assert spans.records() == []
        return
    assert len(mine) == 1, [r.name for r in spans.records()]
    assert mine[0].parent == "test.outer" and mine[0].t1 >= mine[0].t0
    outer = [r for r in spans.records() if r.name == "test.outer"][-1]
    assert outer.t0 <= mine[0].t0 <= mine[0].t1 <= outer.t1


@pytest.fixture
def tmp_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own, every
    program cached however fast it compiled; the process's settings after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
    cc.reset_cache()


def _one_step_window():
    """A run of no followed steps whose window is the next trainer.step."""
    with spans.span("trainer.step", unit=("step", 1)):
        pass
    return {"spans": {"trainer.step": [1.0]},
            "mix": {"followed_steps": 0, "warmup_steps": 0}}


def test_a_load_from_the_cache_lies_inside_its_compile_and_is_no_compile(
        ring, tmp_cache):
    import jax
    assert jit_hooks.install()

    def compile_once(tag):
        with spans.span(tag):
            f = jax.jit(lambda x: jax.numpy.sin(x) * 3 + 2)
            f(np.arange(7, dtype=np.float32)).block_until_ready()

    compile_once("first")
    jax.clear_caches()
    compile_once("second")
    recs = spans.records()
    by = {tag: [r for r in recs if r.parent == tag and r.name.startswith("jit.")]
          for tag in ("first", "second")}
    assert "jit.cache_load" not in {r.name for r in by["first"]}
    assert [r.name for r in by["first"]].count("jit.compile") == 1
    load = [r for r in by["second"] if r.name == "jit.cache_load"]
    comp = [r for r in by["second"] if r.name == "jit.compile"]
    assert len(load) == len(comp) == 1
    assert comp[0].t0 <= load[0].t0 <= load[0].t1 <= comp[0].t1
    # the first compile is set-up's one real compile, the second a load
    reader = harness.load_module(os.path.join(
        REPO, "chipbench", "metrics", "jit.compiled_in_setup.py"), "compiled")
    assert reader.read(_one_step_window()) == 1


def _readers():
    return {n: harness.load_module(os.path.join(
        REPO, "chipbench", "metrics", n + ".py"), "reader_" + n.replace(".", "_"))
        for n in FOUR}


@pytest.mark.parametrize("lost", ["dropped", "full ring, no count"])
def test_a_ring_that_lost_a_record_silences_every_reader(lost, ring, monkeypatch):
    _lazy_import(monkeypatch)
    _initialize(monkeypatch)
    run = _one_step_window()
    readers = _readers()
    got = {n: r.read(run) for n, r in readers.items()}
    assert all(v is not None for v in got.values()), got
    if lost == "dropped":
        monkeypatch.setattr(spans, "_dropped", 1)
    else:   # a program that does not count: a full ring may have dropped
        monkeypatch.delattr(spans, "dropped")
        monkeypatch.setattr(spans, "RING_RECORDS", len(spans.records()))
    assert {n: r.read(run) for n, r in readers.items()} == dict.fromkeys(FOUR)


def test_covered_is_the_union_clipped_to_the_interval():
    R = collections.namedtuple("R", "t0 t1")
    recs = [R(0.0, 2.0), R(1.0, 3.0), R(2.5, 2.6), R(5.0, 9.0), R(-3.0, -1.0)]
    assert setup_spans.covered(recs, 0.5, 6.0) == pytest.approx(2.5 + 1.0)
    assert setup_spans.covered(recs, float("-inf"), 10.0) == pytest.approx(9.0)
    assert setup_spans.covered([], 0.0, 1.0) == 0.0


# ------------------------------------------------------------- rehearsals
REHEARSE = r"""
import json, sys
from chipbench import harness, run, setup_spans
from chipbench.tests.test_run import DATA, fake_chip
seen = {}
drive = harness.drive
def window(*a):
    seen.setdefault("spans", a[-1])
    return drive(*a)
harness.drive = window
r = run.run(["--workload", "tiny.train", "--seed", "2147484011", "--seconds",
             "0.3", "--trace", "1"], bench_path=sys.argv[1], root=DATA,
            require_chip=fake_chip)
got = setup_spans.interval({"spans": seen["spans"].seconds,
                            "mix": harness.load_json(DATA, "tinybench", "traffic",
                                                     "resident.json")})
from mxnet_tpu.observability import spans
imports = [r for r in spans.records() if r.name == "mxnet_tpu.import"]
print(json.dumps({
    "metrics": r["metrics"], "setup_s": r["info"]["setup_s"],
    "interval": got and got[:2],
    "own": got and [(x.t0, x.t1) for x in got[2]],
    "import": [(x.t0, x.t1, x.parent) for x in imports[:1]]}))
"""


def _rehearse(bench, cache, telemetry="1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TELEMETRY=telemetry,
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.Popen([sys.executable, "-c", REHEARSE, str(bench)],
                            cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def _line(proc):
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


def test_a_rehearsal_prints_the_four_and_they_split_set_up(tmp_path):
    """Cold, the four metrics print and the named and unnamed parts add up
    to set-up; warm, set-up compiles nothing; with telemetry off the four
    are left out of the line."""
    bench = harness.load_json(DATA, "tinybench", "BENCHMARK.spans.json")
    real = harness.load_json(REPO, "BENCHMARK.json")
    added = [m for m in real["per_layer"] if m["name"] in FOUR]
    assert [m["name"] for m in added] == list(FOUR)
    bench["per_layer"] += added
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cold = _rehearse(path, tmp_path / "cache")
    quiet = _rehearse(path, tmp_path / "quiet_cache", telemetry="0")
    first = _line(cold)
    warm = _line(_rehearse(path, tmp_path / "cache"))
    off = _line(quiet)

    for line in (first, warm):
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert set(FOUR) <= set(m), sorted(m)
        assert all(math.isfinite(m[k]) and m[k] >= 0 for k in FOUR), m
        assert m["setup.import_s"] > 0 and m["setup.weights_s"] > 0
        start, end = line["interval"]
        # the identity: the unnamed part and the union of the records on the
        # step's thread (counted here by a sweep over their ends) make the
        # interval
        inside = [(max(t0, start), min(t1, end)) for t0, t1 in line["own"]
                  if t1 > start and t0 < end]
        depth, union, since = 0, 0.0, None
        for t, step in sorted([(a, 1) for a, _ in inside] + [(b, -1) for _, b in inside]):
            if depth == 0:
                since = t
            depth += step
            if depth == 0:
                union += t - since
        assert m["setup.unnamed_s"] + union == pytest.approx(end - start, abs=1e-3)
        # run.py's clock starts after the interpreter and stops just before
        # the window's first step
        assert 0 < (end - start) - line["setup_s"] < 2.0
        # the package's own import, recorded at its end under no span
        (t0, t1, parent), = line["import"]
        assert start < t0 < t1 < end and parent is None
    assert first["metrics"]["jit.compiled_in_setup"]["value"] > 0
    assert warm["metrics"]["jit.compiled_in_setup"]["value"] == 0
    assert not set(FOUR) & set(off["metrics"])
    assert "step.mfu" in off["metrics"]      # the line itself is whole
