"""HBM memory observability — footprint ledger, live accounting, OOM forensics.

The perf spine (``xcost``/``perfwatch``) explains *time*; this module is
its byte-side twin. Four surfaces, all strictly host-side (the compiled
HLO is bitwise identical with memwatch on or off — tier-1 guards it):

- **Memory ledger** — per-executable memory rows (argument/output/temp/
  generated-code bytes from XLA's ``memory_analysis``), persisted as
  ``label="memory"`` rows in the same append-only :class:`~.xcost.CostLedger`
  the roofline rows live in, keyed by the StableHLO fingerprint +
  device_kind/n_devices the AOT cache trusts. :func:`record_executable`
  is the one-call tap; ``BucketExecutorCache`` records one row per bound
  serving bucket and ``xcost.capture(compile_for_memory=True)`` closes the
  lazy train-step gap.
- **Live accounting** — :func:`poll_hbm` reads ``device.memory_stats()``
  into the ``mxtpu_hbm_*`` gauges with watermark history. Backends without
  memory_stats (the CPU tier-1 backend) degrade to a synthetic live-set
  sum over trees registered via :func:`track`, so the full path runs in
  every test tier.
- **OOM forensics** — :func:`to_hbm_exhausted` classifies a raw XLA
  RESOURCE_EXHAUSTED at a dispatch boundary, writes an ``mxtpu_oom.json``
  postmortem (:func:`write_postmortem`: footprints, resident bucket
  ladders, top-N largest executables, watermark tail, blame ranking,
  active trace_id) and returns a typed :class:`HBMExhausted` to re-raise.
- **Budget math** — per-chip HBM capacity table + ``MXNET_HBM_BYTES``
  override feed :func:`placement_check`/:func:`fleet_memory_check`, which
  the FleetController and ModelServer consult before binding executables
  a chip cannot hold (refusal reason ``no_memory`` /
  ``MemoryBudgetExceeded`` instead of a device OOM mid-traffic).

``serving.chaos.hbm_pressure`` drives all of this deterministically by
installing a shrunken budget + ballast through :func:`set_pressure`.
"""
from __future__ import annotations

import collections
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..analysis.lockwatch import make_lock
from ..base import MXNetError, get_env, logger, register_config
from . import metrics as _metrics
from . import xcost as _xcost

__all__ = [
    "DEVICE_HBM", "hbm_capacity_bytes", "hbm_budget_bytes",
    "capture_enabled", "HBMExhausted", "is_oom", "to_hbm_exhausted",
    "tree_bytes", "track", "untrack", "live_set_bytes", "poll_hbm",
    "watermark_history", "record_executable", "memory_rows",
    "model_footprint", "trainer_footprint", "placement_check",
    "fleet_memory_check", "set_pressure", "pressure",
    "write_postmortem", "postmortem_path", "top_executables", "blame_table",
]

register_config("MXNET_HBM_BYTES", 0, int,
                "Per-chip HBM budget override in bytes for memory-aware "
                "placement. 0 = use the built-in device_kind capacity "
                "table; devices the table does not know (e.g. the CPU "
                "backend) then have NO budget and memory refusals are "
                "off.")
register_config("MXNET_MEM_CAPTURE", True, bool,
                "Attach XLA memory_analysis to lazy-path cost-ledger rows. "
                "Costs one extra host-side analysis compile per executable "
                "signature (the compiled program actually dispatched is "
                "untouched); set 0 where a second compile is minutes, "
                "not milliseconds.")
register_config("MXNET_OOM_DIR", "", str,
                "Directory the mxtpu_oom.json OOM postmortem artifact is "
                "written to. Empty = current working directory.")

GiB = 1024 ** 3

# (device_kind substring, HBM bytes per chip) — public TPU specs, matched
# most-specific first like xcost.DEVICE_PEAKS. MXNET_HBM_BYTES wins.
DEVICE_HBM = (
    ("v6", 32 * GiB),
    ("v5p", 95 * GiB),
    ("v5e", 16 * GiB),
    ("v5 lite", 16 * GiB),
    ("v5", 95 * GiB),
    ("v4", 32 * GiB),
    ("v3", 32 * GiB),
    ("v2", 16 * GiB),
)

_WATERMARK_KEEP = 256

_lock = make_lock("observability.memwatch._lock")
_LIVE_SETS: Dict[str, Any] = {}        # name -> tree or () -> bytes callable
_WATERMARKS: "collections.deque" = collections.deque(maxlen=_WATERMARK_KEEP)
_SYNTH_PEAK = [0]                      # running peak of the synthetic path
# chaos hook (serving.chaos.hbm_pressure): a shrunken budget and/or a
# ballast reserve, installed/removed atomically via set_pressure()
_PRESSURE: Dict[str, Any] = {"budget_bytes": None, "ballast_bytes": 0}


# --------------------------------------------------------------- budget math
def _device_kind() -> Optional[str]:
    try:
        import jax
        return jax.devices()[0].device_kind
    except Exception:
        return None


def hbm_capacity_bytes(device_kind: Optional[str]) -> Optional[int]:
    """Physical per-chip HBM from the table; None for unknown devices."""
    kind = (device_kind or "").lower()
    for sub, cap in DEVICE_HBM:
        if sub in kind:
            return int(cap)
    return None


def hbm_budget_bytes(device_kind: Optional[str] = None) -> Optional[int]:
    """The per-chip byte budget placement math works against.

    Priority: chaos pressure override > ``MXNET_HBM_BYTES`` > capacity
    table. None = unbudgeted (unknown device, nothing configured):
    memory-aware refusals are off, never guessed.
    """
    with _lock:
        ov = _PRESSURE.get("budget_bytes")
    if ov:
        return int(ov)
    env = int(get_env("MXNET_HBM_BYTES", 0) or 0)
    if env > 0:
        return env
    if device_kind is None:
        device_kind = _device_kind()
    return hbm_capacity_bytes(device_kind)


def capture_enabled() -> bool:
    """Gate for the lazy-path memory_analysis attach (one extra analysis
    compile per executable signature)."""
    return bool(get_env("MXNET_MEM_CAPTURE", True))


def set_pressure(budget_bytes: Optional[int] = None,
                 ballast_bytes: int = 0) -> None:
    """Install (or with defaults, clear) synthetic memory pressure — the
    deterministic lever ``serving.chaos.hbm_pressure`` pulls: an override
    budget and/or a ballast reserve subtracted from every chip's budget."""
    with _lock:
        _PRESSURE["budget_bytes"] = (int(budget_bytes)
                                     if budget_bytes else None)
        _PRESSURE["ballast_bytes"] = max(0, int(ballast_bytes))


def pressure() -> Dict[str, Any]:
    with _lock:
        return dict(_PRESSURE)


# ------------------------------------------------------------- typed errors
class HBMExhausted(MXNetError):
    """A device RESOURCE_EXHAUSTED, re-raised typed at a dispatch boundary
    after the postmortem artifact was written. ``.postmortem`` holds the
    artifact path (None if the write itself failed)."""

    def __init__(self, msg: str, postmortem: Optional[str] = None):
        super().__init__(msg)
        self.postmortem = postmortem


_OOM_MARKERS = ("resource_exhausted", "resource exhausted", "out of memory",
                "allocation failure", "oom")


def is_oom(exc: BaseException) -> bool:
    """True when ``exc`` (or anything on its cause/context chain) is an
    XLA RESOURCE_EXHAUSTED-style allocation failure."""
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        if isinstance(exc, HBMExhausted):
            return True
        txt = ("%s: %s" % (type(exc).__name__, exc)).lower()
        if any(m in txt for m in _OOM_MARKERS):
            return True
        exc = exc.__cause__ or exc.__context__
    return False


def to_hbm_exhausted(exc: BaseException, *, context: str,
                     server=None, trainer=None,
                     model: Optional[str] = None) -> Optional["HBMExhausted"]:
    """Classify ``exc`` at a dispatch boundary.

    Returns a typed :class:`HBMExhausted` (postmortem already written,
    counter bumped) for allocation failures, None for everything else —
    callers re-raise the returned error and leave other exceptions alone.
    Never raises: forensics must not mask the original failure.

    An exception that is ALREADY an :class:`HBMExhausted` (or carries one
    in its cause chain) returns None: an inner boundary wrote the
    postmortem; a second one at an outer layer would overwrite its blame
    table with the outer (less specific) context.
    """
    seen = exc
    for _ in range(16):                     # bounded: cycles can't hang us
        if seen is None:
            break
        if isinstance(seen, HBMExhausted):
            return None
        seen = seen.__cause__ or seen.__context__
    if not is_oom(exc):
        return None
    path = None
    try:
        path = write_postmortem(context, exc=exc, server=server,
                                trainer=trainer, model=model)
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("OOM postmortem write failed: %r", e)
    if _metrics.enabled():
        from . import catalog as _c
        _c.OOM_TOTAL.inc(context=context)
    return HBMExhausted(
        "HBM exhausted during %s%s: %r (postmortem: %s)"
        % (context, (" [model=%s]" % model) if model else "", exc,
           path or "unavailable"),
        postmortem=path)


# ---------------------------------------------------------- live accounting
def tree_bytes(tree) -> int:
    """Total buffer bytes across a pytree of arrays (anything exposing
    ``nbytes``; other leaves count 0)."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        leaves = tree if isinstance(tree, (list, tuple)) else [tree]
    return sum(int(getattr(leaf, "nbytes", 0) or 0) for leaf in leaves)


def track(name: str, tree_or_fn) -> None:
    """Register a live set for the synthetic (no memory_stats) path:
    either a pytree of arrays or a zero-arg callable returning bytes.
    Re-registering a name replaces it."""
    with _lock:
        _LIVE_SETS[str(name)] = tree_or_fn


def untrack(name: str) -> None:
    with _lock:
        _LIVE_SETS.pop(str(name), None)


def live_set_bytes() -> Dict[str, int]:
    """name -> current bytes of every registered live set (a provider that
    raises reports 0 — accounting must never take a process down)."""
    with _lock:
        items = list(_LIVE_SETS.items())
    out: Dict[str, int] = {}
    for name, src in items:
        try:
            out[name] = int(src()) if callable(src) else tree_bytes(src)
        except Exception:
            out[name] = 0
    return out


def poll_hbm(devices: Optional[Sequence] = None) -> Dict[str, Any]:
    """One live-memory sample: per-device in-use/peak/largest published to
    the ``mxtpu_hbm_*`` gauges, a watermark appended to the ring.

    Devices with ``memory_stats()`` report real allocator numbers; the
    rest (CPU) degrade to the synthetic live-set sum (registered trees +
    chaos ballast), with a running synthetic peak — so tier-1 exercises
    gauges, watermarks and budget math end to end.
    """
    if devices is None:
        try:
            import jax
            devices = jax.devices()
        except Exception:
            devices = []
    per_dev: List[Dict[str, Any]] = []
    synthetic = False
    live = None
    for i, d in enumerate(devices):
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            row = {"device": str(getattr(d, "id", i)),
                   "bytes_in_use": int(stats.get("bytes_in_use", 0) or 0),
                   "peak_bytes": int(stats.get("peak_bytes_in_use", 0) or 0),
                   "largest_alloc_bytes": int(
                       stats.get("largest_alloc_size", 0) or 0),
                   "bytes_limit": int(stats.get("bytes_limit", 0) or 0),
                   "synthetic": False}
        else:
            synthetic = True
            if live is None:
                live = live_set_bytes()
                live["ballast"] = int(pressure()["ballast_bytes"])
            in_use = sum(live.values())
            with _lock:
                _SYNTH_PEAK[0] = max(_SYNTH_PEAK[0], in_use)
                peak = _SYNTH_PEAK[0]
            row = {"device": str(getattr(d, "id", i)),
                   "bytes_in_use": in_use, "peak_bytes": peak,
                   "largest_alloc_bytes": max(live.values()) if live else 0,
                   "bytes_limit": int(hbm_budget_bytes() or 0),
                   "synthetic": True}
        per_dev.append(row)
    total = sum(r["bytes_in_use"] for r in per_dev)
    peak = max([r["peak_bytes"] for r in per_dev] or [0])
    largest = max([r["largest_alloc_bytes"] for r in per_dev] or [0])
    if _metrics.enabled():
        from . import catalog as _c
        for r in per_dev:
            _c.HBM_BYTES_IN_USE.set(r["bytes_in_use"], device=r["device"])
        _c.HBM_PEAK_BYTES.set(peak)
        _c.HBM_LARGEST_ALLOC_BYTES.set(largest)
    with _lock:
        _WATERMARKS.append({"time": time.time(), "bytes_in_use": total,
                            "peak_bytes": peak})
    return {"devices": per_dev, "total_bytes_in_use": total,
            "peak_bytes": peak, "largest_alloc_bytes": largest,
            "synthetic": synthetic,
            "budget_bytes": hbm_budget_bytes(),
            "live_sets": live if live is not None else None}


def watermark_history(n: int = _WATERMARK_KEEP) -> List[Dict[str, Any]]:
    """The most recent ``n`` watermark samples, oldest first."""
    with _lock:
        hist = list(_WATERMARKS)
    return hist[-int(n):]


# ------------------------------------------------------------ memory ledger
def record_executable(lowered=None, *, compiled=None,
                      label: str = "", fingerprint: Optional[str] = None,
                      device_kind: Optional[str] = None,
                      platform: Optional[str] = None, n_devices: int = 1,
                      extra: Optional[Dict[str, Any]] = None,
                      ledger=None) -> Optional[Dict[str, Any]]:
    """Persist one ``label="memory"`` ledger row for a compiled program.

    Pass ``lowered`` to have the fingerprint derived (sha256 of the
    StableHLO text — the AOT-cache fingerprint) and, with ``compiled``
    absent and :func:`capture_enabled`, an analysis compile performed.
    Returns the persisted row; None when the ledger/telemetry is off or
    the backend reports nothing. Never raises.
    """
    if not (_metrics.enabled() and _xcost.enabled()) and ledger is None:
        return None
    try:
        if fingerprint is None and lowered is not None:
            import hashlib
            fingerprint = hashlib.sha256(
                lowered.as_text().encode()).hexdigest()
        if compiled is None and lowered is not None and capture_enabled():
            compiled = lowered.compile()
        if compiled is None:
            return None
        mem = _xcost.memory_of(compiled)
        if not mem:
            return None
        row: Dict[str, Any] = {
            "label": "memory", "mem_label": label,
            "fingerprint": fingerprint,
            "device_kind": device_kind, "platform": platform,
            "n_devices": int(n_devices),
            "memory": mem,
            "peak_memory_bytes": (mem["temp_bytes"] + mem["argument_bytes"]
                                  + mem["output_bytes"]),
        }
        if extra:
            row.update(extra)
        led = ledger if ledger is not None else _xcost.get_ledger()
        if led is not None:
            led.append(row)
        return row
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("memory ledger capture failed: %r", e)
        return None


def memory_rows(ledger=None, model: Optional[str] = None
                ) -> List[Dict[str, Any]]:
    """Every memory row in the ledger (rows with an attached ``memory``
    dict: the dedicated ``label="memory"`` rows AND step rows enriched by
    ``xcost.capture``), optionally filtered by serving model name."""
    led = ledger if ledger is not None else _xcost.get_ledger()
    if led is None:
        return []
    out = []
    for r in led.rows():
        if not isinstance(r.get("memory"), dict):
            continue
        if model is not None and r.get("model") != model:
            continue
        out.append(r)
    return out


def top_executables(n: int = 5, ledger=None) -> List[Dict[str, Any]]:
    """The ``n`` largest executables the ledger knows, by peak bytes —
    latest row per fingerprint wins (stale binds must not double-count)."""
    latest: Dict[Any, Dict[str, Any]] = {}
    for r in memory_rows(ledger=ledger):
        latest[r.get("fingerprint") or id(r)] = r
    rows = sorted(latest.values(),
                  key=lambda r: -(r.get("peak_memory_bytes") or 0))
    return rows[:int(n)]


# ----------------------------------------------------------------- footprints
def model_footprint(cache, model: Optional[str] = None,
                    ledger=None) -> Dict[str, Any]:
    """Estimated resident HBM of one serving model's executor cache.

    Params are counted ONCE (every bucket after the first shares them via
    ``Predictor.reshape``); each bucket then adds its incremental bytes —
    temp + output from this model's memory ledger rows when one was
    recorded, else the analytic padded-batch bytes (flagged
    ``estimated``)."""
    params_bytes = len(getattr(cache, "_param_bytes", b"") or b"")
    feat = tuple(getattr(cache, "feature_shape", ()) or ())
    feat_elems = 1
    for x in feat:
        feat_elems *= int(x)
    by_bucket: Dict[int, Dict[str, Any]] = {}
    for r in memory_rows(ledger=ledger, model=model):
        b = r.get("bucket")
        if b is not None:
            by_bucket[int(b)] = r
    buckets: Dict[str, Dict[str, Any]] = {}
    estimated = False
    total = params_bytes
    for b in getattr(cache, "buckets", ()) or ():
        b = int(b)
        row = by_bucket.get(b)
        batch_bytes = b * feat_elems * 4        # float32 padded batch
        if row:
            mem = row["memory"]
            inc = (int(mem.get("temp_bytes", 0))
                   + int(mem.get("output_bytes", 0)) + batch_bytes)
            src = "ledger"
        else:
            inc = batch_bytes
            src = "estimate"
            estimated = True
        buckets[str(b)] = {"bytes": inc, "source": src}
        total += inc
    return {"model": model, "params_bytes": params_bytes,
            "buckets": buckets, "total_bytes": total,
            "chips": int(getattr(cache, "chips", 1) or 1),
            "estimated": estimated}


def trainer_footprint(trainer) -> Dict[str, Any]:
    """Estimated resident HBM of one trainer — delegates to the trainer's
    own ``footprint()`` when it has one (DataParallelTrainer does), else
    falls back to tree sums over conventional attrs."""
    fp = getattr(trainer, "footprint", None)
    if callable(fp):
        try:
            return fp()
        except Exception as e:
            logger.warning("trainer footprint failed: %r", e)
    return {"params_bytes": tree_bytes(getattr(trainer, "_params", None)),
            "total_bytes": tree_bytes(getattr(trainer, "_params", None))}


def per_chip_bytes(footprint: Dict[str, Any], chips: int) -> int:
    """What ONE chip holds when this footprint serves on ``chips`` chips:
    params are replicated per chip; per-bucket batch/temp bytes split
    row-wise across the chips (the rebind contract)."""
    chips = max(1, int(chips))
    params = int(footprint.get("params_bytes", 0) or 0)
    total = int(footprint.get("total_bytes", 0) or 0)
    return params + (total - params + chips - 1) // chips


# ------------------------------------------------------- placement decisions
def placement_check(footprint: Dict[str, Any], chips: int,
                    device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Would this footprint fit on ``chips`` chips? Returns a verdict dict:
    ``ok`` (True when unbudgeted — refusals need a configured budget),
    ``need_bytes`` (per chip), ``budget_bytes`` (per chip, ballast
    already subtracted), ``reason`` (``no_memory`` when it does not fit)."""
    budget = hbm_budget_bytes(device_kind)
    need = per_chip_bytes(footprint, chips)
    if budget is None:
        return {"ok": True, "need_bytes": need, "budget_bytes": None,
                "reason": None}
    avail = int(budget) - int(pressure()["ballast_bytes"])
    ok = need <= avail
    return {"ok": ok, "need_bytes": need, "budget_bytes": avail,
            "reason": None if ok else "no_memory"}


def fleet_memory_check(assignments: Dict[str, Tuple[Dict[str, Any], int]],
                       device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Check a whole placement: ``assignments`` maps model name ->
    (footprint dict, chip count). Returns ``ok`` plus per-model
    violations — the FleetController refuses a resize/grow whose
    post-state has any."""
    violations = []
    for name, (fp, chips) in assignments.items():
        v = placement_check(fp, chips, device_kind=device_kind)
        if not v["ok"]:
            violations.append({"model": name, "chips": int(chips),
                               "need_bytes": v["need_bytes"],
                               "budget_bytes": v["budget_bytes"]})
    return {"ok": not violations, "violations": violations}


# -------------------------------------------------------------- postmortem
def postmortem_path() -> str:
    d = str(get_env("MXNET_OOM_DIR", "") or "") or "."
    return os.path.join(d, "mxtpu_oom.json")


def blame_table(server=None, trainer=None, ledger=None) -> List[Dict[str, Any]]:
    """Ranked HBM holders, largest first: per-model serving footprints,
    the trainer footprint, registered live sets and chaos ballast."""
    holders: List[Dict[str, Any]] = []
    if server is not None:
        for name, st in getattr(server, "_models", {}).items():
            try:
                fp = model_footprint(st.cache, model=name, ledger=ledger)
                holders.append({"holder": "model:%s" % name,
                                "bytes": int(fp["total_bytes"]),
                                "footprint": fp})
            except Exception:
                continue
    if trainer is not None:
        fp = trainer_footprint(trainer)
        holders.append({"holder": "trainer",
                        "bytes": int(fp.get("total_bytes", 0) or 0),
                        "footprint": fp})
    for name, nbytes in live_set_bytes().items():
        holders.append({"holder": "live:%s" % name, "bytes": int(nbytes)})
    ball = int(pressure()["ballast_bytes"])
    if ball:
        holders.append({"holder": "ballast", "bytes": ball})
    holders.sort(key=lambda h: -h["bytes"])
    return holders


def write_postmortem(context: str, *, exc: Optional[BaseException] = None,
                     server=None, trainer=None, model: Optional[str] = None,
                     path: Optional[str] = None, top_n: int = 5) -> str:
    """Write the flight-recorder-style ``mxtpu_oom.json`` artifact and
    return its path. The artifact must stand alone: everything a human
    needs to answer \"who held the HBM\" without the process that died."""
    from . import tracing as _tracing
    doc: Dict[str, Any] = {
        "version": 1,
        "kind": "mxtpu_oom",
        "time": time.time(),
        "context": context,
        "model": model,
        "exception": repr(exc) if exc is not None else None,
        "trace_id": _tracing.current_trace_id(),
        "budget_bytes": hbm_budget_bytes(),
        "pressure": pressure(),
        "live": poll_hbm(),
        "watermarks": watermark_history(32),
        "blame": blame_table(server=server, trainer=trainer),
        "top_executables": [
            {"mem_label": r.get("mem_label") or r.get("label"),
             "fingerprint": r.get("fingerprint"),
             "model": r.get("model"), "bucket": r.get("bucket"),
             "peak_memory_bytes": r.get("peak_memory_bytes"),
             "memory": r.get("memory")}
            for r in top_executables(top_n)],
    }
    if server is not None:
        ladders = {}
        for name, st in getattr(server, "_models", {}).items():
            try:
                cache = st.cache
                fp = model_footprint(cache, model=name)
                ladders[name] = {
                    "ladder": list(cache.buckets),
                    "resident": cache.compiled_buckets(),
                    "chips": int(getattr(cache, "chips", 1) or 1),
                    "per_bucket_bytes": fp["buckets"],
                    "params_bytes": fp["params_bytes"],
                    "total_bytes": fp["total_bytes"],
                }
            except Exception:
                continue
        doc["buckets"] = ladders
    if trainer is not None:
        doc["trainer_footprint"] = trainer_footprint(trainer)
    out = path or postmortem_path()
    d = os.path.dirname(out)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = out + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=repr)
    os.replace(tmp, out)
    top = doc["blame"][0]["holder"] if doc["blame"] else "unknown"
    logger.error("HBM exhausted during %s — postmortem written to %s "
                 "(top holder: %s)", context, out, top)
    return out
