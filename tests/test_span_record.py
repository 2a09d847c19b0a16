"""The program's one step timeline (observability/spans.py): every span
leaves a record in a bounded ring and an annotation in the profiler's trace;
the trainer, the feed and ``jit_hooks`` write into it; the step-time gauges
are fed from it."""
import collections
import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, io as mio, parallel, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import catalog, jit_hooks, spans

pytestmark = pytest.mark.obs


@pytest.fixture
def ring(monkeypatch):
    """A ring of the test's own, so that other tests' records do not show."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setattr(spans, "_ring",
                        collections.deque(maxlen=spans.RING_RECORDS))
    return spans


def _trainer(prefix, **kw):
    mx.random.seed(11)
    net = nn.HybridSequential(prefix=prefix)
    net.add(nn.Dense(8, activation="relu", prefix=prefix + "d0_"),
            nn.Dense(3, prefix=prefix + "d1_"))
    net.initialize(mx.init.Xavier())
    return parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, **kw)


def _batch(b=16, d=6):
    rng = np.random.RandomState(42)
    return rng.randn(b, d).astype("f4"), rng.randint(0, 3, (b,)).astype("f4")


class _Base(mio.DataIter):
    """Endless batches, each ``delay`` seconds in the making."""

    def __init__(self, delay):
        super().__init__(4)
        self.delay = delay

    def next(self):
        time.sleep(self.delay)
        return mio.DataBatch(data=[np.zeros((4, 3), "f4")],
                             label=[np.zeros((4,), "f4")], pad=0, index=None)


# ------------------------------------------------------------------ the ring
def test_record_has_the_parent_and_the_shared_unit(ring):
    with spans.span("outer", unit=("step", 7)):
        with spans.span("middle"):
            with spans.span("inner"):
                spans.record("timed.elsewhere", 1.0, 2.0)
    with spans.span("alone"):
        pass
    by = {r.name: r for r in spans.records()}
    assert [r.name for r in spans.records()] == [
        "timed.elsewhere", "inner", "middle", "outer", "alone"]   # exit order
    assert by["outer"].parent is None and by["middle"].parent == "outer"
    assert by["inner"].parent == "middle"
    assert by["timed.elsewhere"].parent == "inner"
    assert {by[n].unit for n in ("outer", "middle", "inner",
                                 "timed.elsewhere")} == {("step", 7)}
    assert by["alone"].unit is None and by["alone"].parent is None
    assert by["outer"].t0 <= by["middle"].t0 <= by["inner"].t0 \
        <= by["inner"].t1 <= by["middle"].t1 <= by["outer"].t1
    assert by["outer"].thread == threading.get_ident()


def test_two_threads_at_once_keep_their_own_parents_and_units(ring):
    """More threads than cores, a short switch interval, a reader copying the
    ring all the while: no record is lost or crossed."""
    n_threads, n_units = 16, 40
    stop = threading.Event()
    copies = []

    def work(k):
        for m in range(n_units):
            with spans.span("root%d" % k, unit=("batch", k * 1000 + m)):
                with spans.span("child%d" % k):
                    pass

    def reader():
        while not stop.is_set():
            copies.append(len(spans.records()))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        r = threading.Thread(target=reader)
        r.start()
        ts = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        stop.set()
        r.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not r.is_alive() and not any(t.is_alive() for t in ts)
    recs = spans.records()
    assert len(recs) == 2 * n_threads * n_units and copies == sorted(copies)
    for k in range(n_threads):
        roots = [x for x in recs if x.name == "root%d" % k]
        kids = [x for x in recs if x.name == "child%d" % k]
        assert [x.unit for x in roots] == [("batch", k * 1000 + m)
                                           for m in range(n_units)]
        assert [x.unit for x in kids] == [x.unit for x in roots]
        assert {x.parent for x in kids} == {"root%d" % k}
        assert len({x.thread for x in roots + kids}) == 1


def test_ring_is_bounded_and_drops_the_oldest(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    for i in range(7):
        with spans.span("s%d" % i):
            pass
    assert [r.name for r in spans.records()] == ["s3", "s4", "s5", "s6"]
    assert spans.records() is not spans.records()       # a copy
    # the real ring holds a whole benchmark run (the reckoning is in spans.py)
    assert spans.RING_RECORDS >= 1.5 * (5300 + 920 * 8 + 7000)


def test_telemetry_off_records_nothing_and_annotates_nothing(ring, monkeypatch):
    made = []
    monkeypatch.setattr(spans, "TraceAnnotation",
                        lambda *a, **k: made.append(a) or pytest.fail("annotated"))
    monkeypatch.setattr(spans, "StepTraceAnnotation",
                        lambda *a, **k: made.append(a) or pytest.fail("annotated"))
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    n0 = spans.SPAN_MS.count(span="quiet")
    with spans.span("quiet", unit=("step", 1)) as s:
        with spans.span("quiet.child"):
            assert spans.active_spans() == ()
    assert spans.records() == [] and not made
    assert s.t0 is None and s.t1 is None and s.ms is None
    assert spans.SPAN_MS.count(span="quiet") == n0


def test_a_step_root_is_a_step_annotation(ring, monkeypatch):
    made = []

    class Fake:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Fake)
    monkeypatch.setattr(spans, "StepTraceAnnotation", Fake)
    with spans.span("trainer.step", unit=("step", 12)):
        with spans.span("trainer.put"):
            pass
    assert made == [("trainer.step", {"step_num": 12}), ("trainer.put", {})]


# ---------------------------------------------------------------- jit_hooks
def test_jit_events_land_in_the_ring_under_the_open_span(ring):
    import jax
    assert jit_hooks.install()
    assert not hasattr(jit_hooks, "_COMPILE_EVENTS")    # no ring of its own
    with spans.span("compiling", unit=("step", 3)):
        jax.jit(lambda x: x * 3 + 2)(np.arange(5, dtype=np.float32))
    recs = [r for r in spans.records() if r.name.startswith("jit.")]
    assert {"jit.trace", "jit.compile"} <= {r.name for r in recs}
    assert all(r.parent == "compiling" and r.unit == ("step", 3) for r in recs)
    assert all(r.t1 >= r.t0 for r in recs)
    view = jit_hooks.recent_compile_events()
    assert [e["event"] for e in view] == [r.name for r in recs]
    assert all(set(e) == {"event", "t0", "dur_s"} for e in view)


def test_the_ring_outlasts_a_capture_s_worth_of_compiles(ring):
    """The old ring of 64 had forgotten the beginning of set-up by the first
    timed step; this one has not."""
    for i in range(200):
        spans.record("jit.compile", float(i), float(i) + 0.5)
    ev = jit_hooks.recent_compile_events()
    assert len(ev) == 200 and ev[0]["t0"] == 0.0 and ev[0]["dur_s"] == 0.5


# ------------------------------------------------------------------ trainer
def test_trainer_step_spans_nest_and_share_one_step_number(ring):
    x, y = _batch()
    t = _trainer("spn_")
    for _ in range(3):
        t.step(x, y)
    recs = [r for r in spans.records() if r.name.startswith("trainer.")]
    roots = [r for r in recs if r.name == "trainer.step"]
    assert [r.unit for r in roots] == [("step", 1), ("step", 2), ("step", 3)]
    assert all(r.parent is None for r in roots)
    for root in roots:
        kids = [r for r in recs if r.unit == root.unit and r is not root]
        direct = {r.name for r in kids if r.parent == "trainer.step"}
        want = {"trainer.put", "trainer.rng", "trainer.enqueue"}
        if root.unit == ("step", 1):
            want = want | {"trainer.capture"}
        assert direct == want
        assert all(root.t0 <= r.t0 and r.t1 <= root.t1 for r in kids)
        assert sum(r.t1 - r.t0 for r in kids if r.parent == "trainer.step") \
            <= root.t1 - root.t0
    # the capture's children, at most three names, the forward among them
    under = {r.name for r in recs if r.parent == "trainer.capture"}
    assert under == {"trainer.capture.forward", "trainer.capture.graph",
                     "trainer.capture.state"}
    assert {r.unit for r in recs if r.parent == "trainer.capture"} == {("step", 1)}
    # the step's compile is the enqueue's, step 1 only
    comp = [r for r in spans.records() if r.name == "jit.compile"
            and r.parent == "trainer.enqueue"]
    assert comp and {r.unit for r in comp} == {("step", 1)}


def test_a_capture_outside_a_step_stands_alone(ring):
    t = _trainer("spr_")
    t.lower(*_batch())              # captures; no step is open
    t.step(*_batch())
    caps = [r for r in spans.records() if r.name == "trainer.capture"]
    assert [(r.parent, r.unit) for r in caps] == [(None, None)]
    assert "trainer.capture" not in {
        r.name for r in spans.records() if r.unit == ("step", 1)}


def test_breakdown_is_fed_from_the_spans(ring):
    x, y = _batch()
    t = _trainer("spb_")
    for _ in range(4):
        t.step(x, y)
    recs = spans.records()
    mean = lambda name: 1e3 * sum(   # noqa: E731
        r.t1 - r.t0 for r in recs if r.name == name) / 4
    b = t.perf_stats()["buckets_ms"]
    assert b["dispatch"] == pytest.approx(mean("trainer.enqueue"), rel=1e-6)
    assert b["h2d_transfer"] == pytest.approx(mean("trainer.put"), rel=1e-6)
    assert b["host_prep"] > 0


def test_step_time_gauges_read_the_cadence_not_the_enqueue(ring):
    """A loop that reads its loss back and then does 30 ms of something else:
    the step takes 30 ms of wall time and well under that to enqueue."""
    x, y = _batch()
    t = _trainer("spc_")
    t.step(x, y)
    n0, s0 = catalog.STEP_MS.count(), catalog.STEP_MS.totals()[1]
    for _ in range(3):
        time.sleep(0.03)
        float(t.step(x, y))
    roots = [r for r in spans.records() if r.name == "trainer.step"]
    enq = [r for r in spans.records() if r.name == "trainer.enqueue"][-1]
    cadence = roots[-1].t0 - roots[-2].t0
    assert cadence >= 0.03 > enq.t1 - enq.t0
    assert catalog.SAMPLES_PER_SEC.value() == pytest.approx(16 / cadence, rel=1e-6)
    assert catalog.SAMPLES_PER_SEC.value() < 16 / 0.03
    assert catalog.STEP_MS.count() == n0 + 3
    assert catalog.STEP_MS.totals()[1] - s0 >= 3 * 30.0
    assert t.perf_stats()["cadence_ms"] >= 30.0


def test_telemetry_off_steps_leave_no_record(ring, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    n0 = catalog.STEPS_TOTAL.value()
    t = _trainer("spo_")
    for _ in range(2):
        loss = t.step(*_batch())
    assert np.isfinite(float(loss))
    assert spans.records() == [] and catalog.STEPS_TOTAL.value() == n0


# --------------------------------------------------------------------- feed
def test_slow_base_iterator_starves_the_step_and_the_stall_counts(ring):
    n0, s0 = catalog.IO_FEED_STALL_MS.totals()
    feed = mio.DeviceFeedIter(_Base(0.02), depth=2)
    try:
        for _ in range(4):
            feed.next()
    finally:
        feed.close()
    recs = spans.records()
    gets = [r for r in recs if r.name == "feed.get_wait"]
    assert [r.unit for r in gets] == [("batch", m) for m in range(4)]
    assert sum(r.t1 - r.t0 for r in gets) >= 0.06       # it waited on the base
    n1, s1 = catalog.IO_FEED_STALL_MS.totals()
    assert n1 == n0 + 4
    assert s1 - s0 == pytest.approx(1e3 * sum(r.t1 - r.t0 for r in gets), rel=1e-6)
    # the producer's side of the same batches, on another thread
    for m in range(4):
        mine = {r.name: r for r in recs if r.unit == ("batch", m)}
        assert {"feed.base_next", "feed.stage", "feed.put_wait",
                "feed.get_wait"} <= set(mine)
        assert mine["feed.base_next"].t1 - mine["feed.base_next"].t0 >= 0.02
        assert mine["feed.base_next"].thread == mine["feed.stage"].thread \
            != mine["feed.get_wait"].thread
        assert mine["feed.stage"].t1 <= mine["feed.get_wait"].t1


def test_fast_base_iterator_waits_on_the_full_queue(ring):
    feed = mio.DeviceFeedIter(_Base(0.0), depth=1)
    try:
        for _ in range(3):
            feed.next()
            time.sleep(0.05)        # the consumer is the slow side
    finally:
        feed.close()
    puts = [r for r in spans.records() if r.name == "feed.put_wait"]
    assert sum(r.t1 - r.t0 for r in puts) >= 0.08
    gets = [r for r in spans.records() if r.name == "feed.get_wait"]
    assert max(r.t1 - r.t0 for r in gets[1:]) < 0.02


def test_batch_numbers_follow_the_deliveries_across_a_reset(ring):
    class Five(_Base):
        n = 0

        def next(self):
            self.n += 1
            if self.n > 5:
                raise StopIteration
            return super().next()

        def reset(self):
            self.n = 0

    feed = mio.DeviceFeedIter(Five(0.0), depth=2)
    try:
        feed.next()
        feed.next()
        feed.reset()                # staged-but-undelivered batches go
        feed.next()
    finally:
        feed.close()
    gets = [r.unit for r in spans.records() if r.name == "feed.get_wait"]
    assert gets == [("batch", 0), ("batch", 1), ("batch", 2)]
    staged = [r.unit[1] for r in spans.records() if r.name == "feed.stage"]
    assert staged[:2] == [0, 1] and 2 in staged[2:]    # renumbered from 2


def test_prefetching_iter_counts_its_stall():
    data = np.zeros((8, 3), "f4")
    it = mio.PrefetchingIter(mio.NDArrayIter(data, np.zeros((8,), "f4"), 4))
    n0, _ = catalog.IO_FEED_STALL_MS.totals()
    try:
        it.next()
        it.next()
    finally:
        it.close()
    assert catalog.IO_FEED_STALL_MS.totals()[0] == n0 + 2


# ----------------------------------------------------------------- profiler
def test_xplane_host_plane_holds_the_program_s_span_names(ring, tmp_path):
    """Under jax.profiler.start_trace, on the CPU backend: the spans are
    annotations in /host:CPU (read as the benchmark reads a trace)."""
    import jax
    from chipbench import trace
    x, y = _batch()
    t = _trainer("spx_")
    t.step(x, y)
    feed = mio.DeviceFeedIter(_Base(0.0), depth=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            feed.next()
            float(t.step(x, y))
    finally:
        jax.profiler.stop_trace()
        feed.close()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                    "*.xplane.pb"))
    [host] = [p for p in trace.read(path) if p["name"] == trace.HOST_PLANE]
    events = [(host["meta"].get(mid, ("",))[0], s, d)
              for evs in host["lines"].values() for s, d, mid in evs]
    names = {n for n, _s, _d in events}
    assert {"trainer.step", "trainer.put", "trainer.rng", "trainer.enqueue",
            "feed.get_wait", "feed.base_next", "feed.stage"} <= names
    # on one clock: every enqueue lies inside some step of the trace
    steps = [(s, s + d) for n, s, d in events if n == "trainer.step"]
    assert len(steps) == 3
    for n, s, d in events:
        if n == "trainer.enqueue":
            assert any(a <= s and s + d <= b for a, b in steps)


def test_profiler_merge_puts_device_lanes_on_the_host_clock(ring, tmp_path):
    """mx.profiler with xla_trace_dir: a span of the program shows twice in
    the dumped trace, as the profiler's own host event and as the XLA trace's
    annotation, and the merge lines the two up by the anchor (it used to put
    the XLA trace's first event at zero)."""
    import jax.numpy as jnp
    profiler.set_config(profile_all=True, filename=str(tmp_path / "p.json"),
                        xla_trace_dir=str(tmp_path / "xla"))
    profiler.start()
    try:
        time.sleep(0.05)
        with spans.span("aligned.span"):
            jnp.ones((32, 32)).dot(jnp.ones((32, 32))).block_until_ready()
            time.sleep(0.01)
    finally:
        profiler.stop()
        profiler.set_config(filename="profile.json", xla_trace_dir=None)
    evs = [e for e in profiler._prof.events if e.get("name") == "aligned.span"]
    profiler._prof.events = []
    own = [e for e in evs if "lane" not in e.get("args", {})]
    lane = [e for e in evs if e.get("args", {}).get("lane") == "xla-device"]
    assert len(own) == 1 and len(lane) == 1
    assert own[0]["ts"] >= 50e3                   # it began 50 ms after start()
    assert abs(lane[0]["ts"] - own[0]["ts"]) < 2e3, (lane[0]["ts"], own[0]["ts"])
