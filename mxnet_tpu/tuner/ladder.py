"""Importable trial harness — the perf-lab ladder as a library.

``tools/perf_lab.py`` grew the repo's only measured-trial machinery as one
monolithic ``main()``; this module is that machinery as data + functions so
the autotuner (``tuner.tune``) and the CLI share ONE implementation:

- :class:`VariantSpec` — ladder variants as data (``"NHWC:512"``,
  ``"RMT:512"`` = NHWC + full remat, ``"IMP:32"`` = the
  imperative-dispatch lab);
- :func:`run_variant` / :func:`run_ladder` — build + measure one/all
  ResNet-50 variants in ONE process (one process holds the chip),
  emitting the CLI's historical JSON lines;
- :func:`measure_step` — the timing core (first-call compile, warmup,
  timed window) on any prebuilt trainer — what the tuner's measure phase
  runs on its top-K candidates;
- :func:`profile_step` / :func:`hlo_audit` / :func:`imperative_lab` — the
  diagnostics that used to live inline in perf_lab's tail.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["DEFAULT_VARIANTS", "SEED_VARIANTS", "VariantSpec",
           "parse_variants", "measure_step",
           "run_variant", "run_ladder", "profile_step", "hlo_audit",
           "imperative_lab"]

# the historical default ladder and the staged seed ladder the ROADMAP
# names for the live-chip window (RMT:512, NHWC:512 + the NCHW
# reference point; convert triage = hlo_audit on the last variant)
DEFAULT_VARIANTS = "NCHW:256,NHWC:256,NHWC:512,NHWC:1024"
SEED_VARIANTS = "NCHW:256,NHWC:512,RMT:512"


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _log_stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class VariantSpec:
    """One ladder variant as data. ``token`` spellings:

    ``NCHW:B`` / ``NHWC:B``  plain layout at batch B
    ``RMT:B``                NHWC + full forward rematerialization (the
                             batch-512 fit-without-spilling lever)
    ``IMP:B``                imperative-dispatch lab (no trainer built)
    """

    __slots__ = ("label", "layout", "batch", "remat", "imperative")

    def __init__(self, label: str, layout: str, batch: int,
                 remat=None, imperative: bool = False):
        self.label = label
        self.layout = layout
        self.batch = int(batch)
        self.remat = remat
        self.imperative = bool(imperative)

    @classmethod
    def parse(cls, token: str) -> "VariantSpec":
        try:
            label, b = token.strip().split(":")
            batch = int(b)
        except ValueError:
            raise MXNetError(f"bad variant token {token!r} (want LABEL:B)")
        if label == "IMP":
            return cls("IMP", "IMP", batch, imperative=True)
        remat = "full" if label == "RMT" else None
        layout = "NHWC" if remat else label
        if layout not in ("NCHW", "NHWC"):
            raise MXNetError(f"unknown variant label {label!r}")
        return cls(label, layout, batch, remat=remat)

    @property
    def variant(self) -> str:
        return f"{self.label}:{self.batch}"

    def to_candidate(self):
        """The tuner-space view of this variant (IMP has none)."""
        from .space import Candidate
        if self.imperative:
            raise MXNetError("IMP variants have no candidate equivalent")
        return Candidate(self.batch, self.layout, remat=self.remat)

    def __repr__(self) -> str:
        return f"VariantSpec({self.variant})"


def parse_variants(spec: str) -> List[VariantSpec]:
    return [VariantSpec.parse(tok) for tok in str(spec).split(",")
            if tok.strip()]


# ---------------------------------------------------------------- measuring
def measure_step(trainer, x, y, *, steps: int, warmup: int,
                 feed: bool = False,
                 prefetch_depth: int = 0) -> Dict[str, Any]:
    """Timing core on a prebuilt trainer and a host batch: first call
    (the compile), device staging, warmup, timed window. Returns img_s/step_ms/compile_s/
    loss plus the staged device arrays under ``xd``/``yd`` (for follow-up
    diagnostics on the same buffers).

    ``feed=False`` (default, the historical perf_lab semantics) stages the
    batch device-resident once — the feed cannot be the bottleneck and
    ``prefetch_depth`` is ignored. ``feed=True`` pays the host→device
    transfer every step: through ``io.prefetch_to_device`` at
    ``prefetch_depth >= 1`` (async, overlapped), or synchronously per step
    at depth 0 — so a no-prefetch candidate competes on the same feed
    terms instead of silently riding the resident path."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    if steps < 1:
        raise MXNetError("measure_step needs steps >= 1, got %d" % steps)
    t0 = time.perf_counter()
    loss = trainer.step(x, y)
    float(loss)
    compile_s = time.perf_counter() - t0
    spec = NamedSharding(trainer.mesh, P(trainer._axis))
    xd = jax.device_put(x, spec)
    yd = jax.device_put(y, spec)
    batch = int(x.shape[0])
    # one timing core, three batch sources — the protocol (warmup, loss
    # barrier, timed window) must stay bit-identical across modes or
    # cross-mode comparisons skew
    if feed and prefetch_depth > 0:
        from mxnet_tpu.io import prefetch_to_device

        def src(n):
            for _ in range(n):
                yield (x, y)

        it = iter(prefetch_to_device(src(warmup + steps + 1), sharding=spec,
                                     depth=prefetch_depth))
        next(it)                                # pipeline fill

        def next_batch():
            return next(it)
    elif feed:
        # depth 0 under feed: synchronous per-step staging (a fair
        # "no prefetch" baseline that still pays the wire)
        def next_batch():
            return jax.device_put(x, spec), jax.device_put(y, spec)
    else:
        def next_batch():
            return xd, yd
    for _ in range(warmup):
        loss = trainer.step(*next_batch())
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(*next_batch())
    float(loss)
    dt = max(time.perf_counter() - t0, 1e-9)
    return {"img_s": steps * batch / dt, "step_ms": 1e3 * dt / steps,
            "compile_s": compile_s, "loss": float(loss),
            "xd": xd, "yd": yd, "measure_s": dt}


def run_variant(spec: VariantSpec, *, steps: int, warmup: int, image: int,
                on_accel: bool) -> Tuple[Dict[str, Any], Optional[Tuple]]:
    """Build + measure one ResNet-50 ladder variant. Returns
    ``(result_line, ctx)`` where ``result_line`` is exactly the historical
    perf_lab JSON line (``variant``/``img_s``/``step_ms``/``compile_s``/
    ``analytic_tflops``/``loss``) and ``ctx = (trainer, xd, yd, layout,
    batch)`` feeds the profile/HLO-audit diagnostics. Raises on failure —
    :func:`run_ladder` turns that into the historical error line."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    np.random.seed(0)
    mx.random.seed(0)
    layout, batch = spec.layout, spec.batch
    net = vision.resnet50_v1(classes=1000, layout=layout)
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    # ladder variants are explicit hand-flag reference points: the graph
    # passes are pinned OFF so NHWC:512 measures exactly NHWC:512 (the
    # default pipeline would rewrite the NCHW rung to NHWC and collapse
    # distinct rungs onto one program); the emitted row records that
    # provenance
    trainer = parallel.DataParallelTrainer(
        net, loss_fn, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        compute_dtype="bfloat16" if on_accel else None,
        remat=spec.remat, passes=False)
    shape = (batch, image, image, 3) if layout == "NHWC" \
        else (batch, 3, image, image)
    x = np.random.uniform(-1, 1, shape).astype("float32")
    y = np.random.randint(0, 1000, (batch,)).astype("float32")

    m = measure_step(trainer, x, y, steps=steps, warmup=warmup)
    flops = 12.3e9 * (image / 224.0) ** 2 * batch * (steps / m["measure_s"])
    result = {
        "variant": spec.variant, "img_s": round(m["img_s"], 1),
        "step_ms": round(m["step_ms"], 2),
        "compile_s": round(m["compile_s"], 1),
        "analytic_tflops": round(flops / 1e12, 1),
        "loss": m["loss"],
        "passes": trainer.passes_provenance(),
    }
    return result, (trainer, m["xd"], m["yd"], layout, batch)


def run_ladder(variants: Sequence[VariantSpec], *, steps: int, warmup: int,
               image: int, on_accel: bool,
               emit: Callable[[Dict[str, Any]], None],
               log: Callable[[str], None] = _log_stderr
               ) -> Tuple[List[Dict[str, Any]], Optional[Tuple]]:
    """Run every variant in sequence (one process holds the chip),
    emitting one dict per variant — successes and the historical
    ``{"variant": ..., "error": ...}`` failure lines alike. Returns
    ``(results, last_ctx)``; ``last_ctx`` is the final successful
    variant's ``(trainer, xd, yd, layout, batch)`` for the profile/HLO
    diagnostics."""
    results: List[Dict[str, Any]] = []
    last: Optional[Tuple] = None
    for spec in variants:
        t_var = time.perf_counter()
        if spec.imperative:
            # imperative-dispatch lab (north-star config #3, SURVEY hard
            # part #2): per-op dispatch rate + LSTM-PTB step time with the
            # un-hybridized imperative path vs the hybridized one
            try:
                res = imperative_lab(spec.batch or 32)
            except Exception as e:
                res = {"variant": f"IMP:{spec.batch}",
                       "error": repr(e)[:300]}
            emit(res)
            results.append(res)
            continue
        try:
            res, ctx = run_variant(spec, steps=steps, warmup=warmup,
                                   image=image, on_accel=on_accel)
            last = ctx
        except Exception as e:
            res = {"variant": spec.variant, "error": repr(e)[:300]}
        emit(res)
        results.append(res)
        log(f"# variant took {time.perf_counter() - t_var:.0f}s total")
    return results, last


# -------------------------------------------------------------- diagnostics
def profile_step(trainer, xd, yd, steps: int = 10) -> Dict[str, Any]:
    """On-chip profile: where does the step actually spend time? Traces
    ``steps`` steps and aggregates device-op durations from the chrome
    trace. Raises on failure (callers emit the historical error line)."""
    import glob
    import gzip
    import tempfile
    from collections import Counter
    import jax
    tdir = tempfile.mkdtemp(prefix="perf_lab_trace_")
    with jax.profiler.trace(tdir):
        for _ in range(steps):
            loss = trainer.step(xd, yd)
        float(loss)
    paths = glob.glob(os.path.join(
        tdir, "plugins", "profile", "*", "*.trace.json.gz"))
    agg = Counter()
    total = 0.0
    for pth in paths:
        with gzip.open(pth, "rt") as f:
            data = json.load(f)
        pids = {p.get("args", {}).get("name", ""): p.get("pid")
                for p in data.get("traceEvents", [])
                if p.get("ph") == "M" and p.get("name") == "process_name"}
        device_pids = {pid for nm, pid in pids.items()
                       if "TPU" in str(nm) or "/device" in str(nm)}
        for e in data.get("traceEvents", []):
            if (e.get("ph") == "X" and e.get("pid") in device_pids
                    and isinstance(e.get("dur"), (int, float))):
                agg[e.get("name", "?")] += e["dur"]
                total += e["dur"]
    top = [{"op": k[:80], "ms": round(v / 1e3, 2),
            "pct": round(100 * v / total, 1)}
           for k, v in agg.most_common(18)]
    return {"profile_top_ops": top,
            "profile_total_ms": round(total / 1e3, 1),
            "trace_dir": tdir}


def hlo_audit(trainer, xd, yd, hlo_path: str = "/tmp/perf_lab_hlo.txt"
              ) -> Dict[str, Any]:
    """Fusion/convert triage over the compiled HLO (dumped to ``hlo_path``).
    A raw convert COUNT is misleading (r4 counted 950, but converts INSIDE
    fused computations ride an existing HBM pass for free) — what costs
    bandwidth is a convert that is its own top-level instruction in the
    ENTRY computation: a dedicated read+write of the tensor. Classify by
    computation and weigh the standalone ones by element count. Raises on
    failure (callers emit the historical error line)."""
    from collections import Counter
    txt = trainer.lower(xd, yd).compile().as_text()
    with open(hlo_path, "w") as f:
        f.write(txt)
    c = Counter()
    entry_convert_elems = 0
    entry_converts = 0
    fused_converts = 0
    cur_entry = False
    for line in txt.splitlines():
        if line and not line[0].isspace():
            # a computation header (or closing brace) at column 0:
            # "ENTRY %main... {" vs "%fused_computation.N (...) {"
            if line.startswith("ENTRY"):
                cur_entry = True
            elif line.startswith("%"):
                cur_entry = False
            continue
        mo = re.match(r"^\s+(?:ROOT )?%?\S+ = (\S+?)\[([\d,]*)\]\S* "
                      r"(\w[\w\-]*)\(", line)
        if not mo:
            continue
        dtype_shape, dims, op = mo.groups()
        c[op] += 1
        if op == "convert":
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            if cur_entry:
                entry_converts += 1
                entry_convert_elems += n
            else:
                fused_converts += 1
    audit = {k: c[k] for k in
             ("transpose", "convert", "convolution", "fusion",
              "custom-call", "all-reduce", "copy") if k in c}
    audit["convert_standalone_entry"] = entry_converts
    audit["convert_standalone_entry_melems"] = round(
        entry_convert_elems / 1e6, 2)
    audit["convert_inside_fusions"] = fused_converts
    return {"hlo_audit": audit, "hlo_path": hlo_path}


def imperative_lab(batch: int = 32) -> Dict[str, Any]:
    """Imperative-dispatch measurements (VERDICT r4 next #4).

    The reference's risk case (SURVEY hard part #2,
    src/imperative/imperative.cc:38-120): per-op Python dispatch on small
    tensors, and the LSTM-PTB training step (north-star config #3) run
    UN-hybridized — every op a separate cached-jit dispatch — vs
    hybridized into one program. Returns one result dict:

        {"variant": "IMP:32", "elemwise_ops_per_s": ..., "chain10_ms": ...,
         "ptb_imperative_ms": ..., "ptb_hybrid_ms": ..., "imp_vs_hybrid": ...}

    Contract tracked by the ladder: imperative within 5x of hybrid at PTB
    sizes (batch 32, bptt 35, 2x200 LSTM, vocab 10k).
    """
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    # ---- per-op dispatch rate on small tensors -----------------------
    a = nd.array(np.random.randn(64, 64).astype("float32"))
    b = nd.array(np.random.randn(64, 64).astype("float32"))
    for _ in range(20):                      # warm the jitted-op caches
        c = a + b
    c.wait_to_read()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        c = a + b
    c.wait_to_read()
    elemwise_rate = n / (time.perf_counter() - t0)

    def chain(x):
        for _ in range(10):                  # 10 distinct dispatches
            x = nd.relu(x + 1.0) * 0.5
        return x
    chain(a).wait_to_read()
    t0 = time.perf_counter()
    reps = 100
    for _ in range(reps):
        out = chain(a)
    out.wait_to_read()
    chain10_ms = 1e3 * (time.perf_counter() - t0) / reps

    # ---- LSTM-PTB step: imperative vs hybridized ----------------------
    VOCAB, T, H, L = 10000, 35, 200, 2

    class PTBModel(gluon.HybridBlock):
        """Embedding -> 2x200 LSTM -> vocab decoder; states built inline
        so the same block runs imperatively AND hybridized."""

        def __init__(self, prefix):
            super().__init__(prefix=prefix)
            with self.name_scope():
                self.emb = gluon.nn.Embedding(VOCAB, H)
                self.lstm = gluon.rnn.LSTM(H, num_layers=L, layout="NTC")
                self.dec = gluon.nn.Dense(VOCAB, flatten=False)

        def hybrid_forward(self, F, x):
            h = self.emb(x)
            states = [F.zeros(shape=(L, batch, H)),
                      F.zeros(shape=(L, batch, H))]
            h = self.lstm(h, *states)
            if isinstance(h, (list, tuple)):
                h = h[0]
            return self.dec(h)

    def build(prefix):
        net = PTBModel(prefix)
        net.initialize(mx.init.Xavier())
        return net

    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, VOCAB, (batch, T)).astype("float32"))
    y = nd.array(rng.randint(0, VOCAB, (batch, T)).astype("float32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def step_time(net, steps=8, warmup=3):
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})

        def one():
            with autograd.record():
                out = net(x)
                l = loss_fn(out, y)
            l.backward()
            trainer.step(batch)
            return l
        for _ in range(warmup):
            one().wait_to_read()
        t0 = time.perf_counter()
        for _ in range(steps):
            l = one()
        l.wait_to_read()
        return 1e3 * (time.perf_counter() - t0) / steps

    imp_net = build("implab_")
    imp_ms = step_time(imp_net)
    hyb_net = build("hyblab_")
    hyb_net(x).wait_to_read()     # materialize params imperatively first
    hyb_net.hybridize()
    hyb_ms = step_time(hyb_net)

    return {
        "variant": f"IMP:{batch}",
        "elemwise_ops_per_s": round(elemwise_rate, 1),
        "chain10_ms": round(chain10_ms, 3),
        "ptb_imperative_ms": round(imp_ms, 2),
        "ptb_hybrid_ms": round(hyb_ms, 2),
        "imp_vs_hybrid": round(imp_ms / hyb_ms, 2) if hyb_ms else None,
    }
