"""What one run of one cell does, as functions: find the cell's files by
name, build the program exactly as a user would, follow its first steps,
drive the timed window, trace a short tail, run the plain reference.

Data decides everything that belongs to one configuration, one traffic mix
or one per-layer metric (README.md). From the program this file takes the
system under test (``gluon.model_zoo`` block, ``parallel.DataParallelTrainer``,
``io.DeviceFeedIter``) and the ``jit_hooks`` compile counter, nothing else.
"""
import contextlib
import glob
import importlib
import importlib.util
import json
import os
import queue
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SPANS = ("feed.next", "trainer.step", "watcher.wait")
# the program's own spans on the step's thread and the consumer's side of the
# feed (PERF.md section 3): what a traced run's idle gaps are named by
PROGRAM_SPANS = ("trainer.step", "trainer.put", "trainer.rng", "trainer.enqueue",
                 "trainer.capture", "feed.get_wait")
TRACE_SECONDS = 3.0   # the traced tail: some tens of steps, a file of ~50 MB


class NoChip(SystemExit):
    pass


# ------------------------------------------------------------ finding files
def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench, workload, root=ROOT):
    """The workload's entry with its configuration, mix, limits and
    reference module, each found by the name ``BENCHMARK.json`` gives."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("unknown workload %r; BENCHMARK.json has %s"
                         % (workload, sorted(cells)))
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(root, conf["file"])
    base = os.path.join(root, bench["paths"][0])
    mix = load_json(base, "traffic", cell["traffic"] + ".json")
    limits = load_json(base, "limits", workload + ".json")["limits"]
    ref = load_module(_own_or_shared(base, "reference", cfg["reference"] + ".py"),
                      "chipbench_reference_" + cfg["reference"])
    return cell, cfg, mix, limits, ref


def _own_or_shared(base, *parts):
    """The bench's own file, or (a test bench borrows them) this one's."""
    path = os.path.join(base, *parts)
    return path if os.path.exists(path) else os.path.join(HERE, *parts)


def metric_readers(bench, workload, root=ROOT):
    """{metric name: read(run)} for the per-layer metrics of this cell."""
    base = os.path.join(root, bench["paths"][0])
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        mod = load_module(_own_or_shared(base, "metrics", m["name"] + ".py"),
                          "chipbench_metric_" + m["name"].replace(".", "_"))
        out[m["name"]] = mod.read
    return out


# ------------------------------------------------------------------ chip
def require_chip(chips):
    """The devices to run on and their row of peaks.json; no result line and
    a non-zero exit without a TPU, on an unknown kind, or on too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip("chipbench: JAX found no TPU (platform %r); a time from "
                     "another backend is not a device number" % devs[0].platform)
    peaks = load_json(HERE, "peaks.json")
    if devs[0].device_kind not in peaks:
        raise NoChip("chipbench: device_kind %r is not in chipbench/peaks.json"
                     % devs[0].device_kind)
    if len(devs) < chips:
        raise NoChip("chipbench: the cell asks for %d chips, JAX found %d"
                     % (chips, len(devs)))
    return devs[:chips], peaks[devs[0].device_kind]


def enable_cache():
    """The program's own rule for the persistent compile cache (a fixed
    directory in the checkout, or JAX_COMPILATION_CACHE_DIR), and every
    program in it however fast it compiled: the capture's op-by-op forward
    is some sixty sub-second compiles."""
    import jax
    from mxnet_tpu import base
    path = base.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# ----------------------------------------------------------------- program
def _to_program(leaf, weight_layout):
    """Reference layouts (HWIO) to the program's conv weight layout, where
    the configuration gives one."""
    if leaf.ndim != 4 or weight_layout is None:
        return leaf
    return leaf.transpose({"OHWI": (3, 0, 1, 2), "OIHW": (3, 2, 0, 1)}[weight_layout])


def _from_program(leaf, weight_layout):
    """The program's conv weight layout back to the reference's (HWIO)."""
    if leaf.ndim != 4 or weight_layout is None:
        return leaf
    return leaf.transpose({"OHWI": (1, 2, 3, 0), "OIHW": (2, 3, 1, 0)}[weight_layout])


def build_program(cfg, ref, seed, devices):
    """Net and trainer built as a user builds them, holding the seed's
    weights. Returns (net, trainer, mesh, trainable flags)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from . import traffic
    mx.random.seed(int(seed) & 0x7FFFFFFF)
    mod, fn = cfg["builder"].split(":")
    net = getattr(importlib.import_module(mod), fn)(**cfg["builder_kwargs"])
    net.initialize(mx.init.Xavier())
    # every leaf in ONE jitted call from the seed, already in the layout and
    # type the program keeps it in; set_data also settles deferred shapes, so
    # no forward pass is spent on initialisation
    specs = ref.leaf_specs(cfg)
    make = jax.jit(lambda k: [_to_program(l, cfg.get("weight_layout"))
                              for l in ref.init(cfg, k)])
    leaves = make(traffic.seed_key(seed))
    params = list(net.collect_params().values())
    if len(params) != len(specs):
        raise SystemExit("chipbench: the program's block has %d parameters, "
                         "the reference %d" % (len(params), len(specs)))
    for p, leaf, (kind, _shape, trainable) in zip(params, leaves, specs):
        known = all(a in (0, b) for a, b in zip(p.shape or leaf.shape, leaf.shape))
        if (p.shape and len(p.shape) != leaf.ndim) or not known \
                or trainable != (p.grad_req != "null"):
            raise SystemExit("chipbench: parameter %s %r does not match the "
                             "reference's %s %r" % (p.name, p.shape, kind,
                                                    tuple(leaf.shape)))
        p.set_data(mx.nd.array(leaf))
    opt = dict(cfg["optimizer"])
    mesh = parallel.local_mesh("dp", devices=list(devices))
    trainer = parallel.DataParallelTrainer(
        net, getattr(gluon.loss, cfg["loss"])(**cfg.get("loss_kwargs", {})),
        opt.pop("name"), opt,
        compute_dtype=cfg["compute_dtype"], mesh=mesh,
        **cfg.get("trainer_kwargs", {}))
    return net, trainer, mesh, [t for _k, _s, t in specs]


def host_leaves(net):
    """Every parameter of the net as a host float32 array, in order."""
    return [p.data().asnumpy() for p in net.collect_params().values()]


def host_norms(a, b, scale=1.0):
    import numpy as np
    return [float(np.sqrt(np.sum(np.square(
        x.astype(np.float64) - y.astype(np.float64))))) * scale
        for x, y in zip(a, b)]


def follow_program(net, trainer, feed, trainable, lr, steps, weight_layout=None):
    """The program's first steps through the window's own call and feed:
    each loss, every trainable leaf's first update and its change after the
    last step (their norms, the first over lr, and whole, as host float32 in
    the reference's layout), and the first step's change of every other leaf
    (the running statistics). The same trainer goes on into the window."""
    import numpy as np

    def split(leaves):
        return ([l for l, t in zip(leaves, trainable) if t],
                [l.astype(np.float64) for l, t in zip(leaves, trainable) if not t])

    def changes(w):
        return [_from_program(b - a, weight_layout) for a, b in zip(w0, w)]

    w0, s0 = split(host_leaves(net))
    losses, grad1, update1, state1 = [], None, None, None
    for n in range(steps):
        x, y = feed.next()
        losses.append(float(trainer.step(x, y)))
        if n == 0 or n == steps - 1:
            trainer.sync_to_net()
            w, s = split(host_leaves(net))
            if n == 0:
                grad1 = host_norms(w0, w, 1.0 / lr)
                update1 = changes(w)
                state1 = [b - a for a, b in zip(s0, s)]
    return {"loss": losses, "grad1": grad1, "dparam": host_norms(w0, w),
            "update1": update1, "change": changes(w), "state1": state1}


# ------------------------------------------------------------------ window
class Spans:
    """The harness's own spans, in memory only: name -> [seconds]. They
    count the window's calls for ``program_spans.select``; the trace is
    annotated by the program's spans."""

    def __init__(self):
        self.seconds = {n: [] for n in SPANS}

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        yield
        self.seconds[name].append(time.perf_counter() - t0)


def drive(trainer, feed, seconds, in_flight, spans):
    """Issue steps for ``seconds`` with at most ``in_flight`` outstanding; a
    watcher waits on each returned loss in order and stamps its completion,
    so the issuing loop waits only when the pipeline is full. The window runs
    from the first issue to the last completion, and all its steps count."""
    import numpy as np
    slots = threading.Semaphore(in_flight)
    pending = queue.Queue()
    done, losses = [], []

    def watch():
        while True:
            loss = pending.get()
            if loss is None:
                return
            with spans.span("watcher.wait"):
                losses.append(float(np.asarray(loss)))
            done.append(time.perf_counter())
            slots.release()

    watcher = threading.Thread(target=watch, name="chipbench-watcher")
    watcher.start()
    t0 = time.perf_counter()
    try:
        while True:
            slots.acquire()
            if time.perf_counter() - t0 >= seconds:
                break
            with spans.span("feed.next"):
                x, y = feed.next()
            with spans.span("trainer.step"):
                loss = trainer.step(x, y)
            pending.put(loss)
    finally:
        pending.put(None)
        watcher.join()
    stamps = [t0] + done
    return {"steps": len(done), "seconds": done[-1] - t0,
            "step_s": [b - a for a, b in zip(stamps, stamps[1:])],
            "failed": sum(1 for v in losses if v != v or abs(v) == float("inf"))}


def traced_tail(trainer, feed, in_flight, spans):
    """A short window of the same loop under the profiler, whole steps only
    (the pipeline is empty when it starts and when it stops). Returns the
    xplane file's path, the window's length on the host clock, and the
    directory to delete."""
    import jax
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        drive(trainer, feed, TRACE_SECONDS, in_flight, spans)
        window_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    return (files[0] if files else None), window_s, tmp


def memory_peak_bytes(devices):
    """Peak on the fullest chip, read when the window has closed. The TPU
    allocator reports live buffers (``bytes_in_use``) and what XLA reserved
    for its loaded programs' temporaries (``bytes_reserved``) apart, and they
    are disjoint: while the step is loaded the chip holds their sum. The
    capture's op-by-op forward at the full batch, before the step exists,
    peaks in live buffers alone. The peak is the larger of the two moments
    (the sum of the two PEAKS would pass the chip's 16 GB)."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)),
                   int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0)))
    return peak


# --------------------------------------------------------------- reference
def follow_reference(cfg, mix, ref, seed, n_rows, sharding, steps,
                     rounding=None, rows=None, memo=None):
    """The plain reference over the same weights and the same first batches,
    made anew from the seed. ``rounding`` / ``rows`` are the control's lower
    precision and the planted batch fault; a run leaves both None. ``memo``
    (a dict) keeps the jitted functions for a next seed in the same process."""
    import functools
    import jax
    from . import follow, traffic
    trainable = [t for _k, _s, t in ref.leaf_specs(cfg)]
    memo = {} if memo is None else memo
    if "step" not in memo:
        memo["init"] = jax.jit(lambda k: ref.init(cfg, k))
        memo["batch"] = jax.jit(
            lambda key, i: traffic.reference_batch(mix, cfg, key, i, n_rows),
            out_shardings=(sharding, sharding))
        memo["step"] = follow.make_step(
            functools.partial(ref.loss_fn, cfg, rounding=rounding, rows=rows),
            trainable, cfg["optimizer"])
    key = traffic.seed_key(seed)
    batches = (memo["batch"](key, i) for i in range(steps))
    return follow.follow(*memo["step"], memo["init"](key), trainable, batches,
                         cfg["optimizer"]["learning_rate"])


def percentile(values, q):
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))]


def stderr(*a):
    print(*a, file=sys.stderr, flush=True)
