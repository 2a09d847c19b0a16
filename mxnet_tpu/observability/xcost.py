"""XLA cost ledger — per-executable compile-time performance facts.

Every compiled train step carries a free, exact self-description: XLA's
``cost_analysis()`` knows the FLOPs, the bytes moved through HBM and the
transcendental count of the whole fused program. This module makes it a
first-class, persistent artifact:

- :func:`analyze_cost` turns a raw ``cost_analysis()`` dict into a row with
  derived quantities — arithmetic intensity (FLOPs/byte), the device's
  roofline ridge point (peak FLOPs ÷ peak HBM bandwidth) and a
  **compute-bound / memory-bound** classification, plus the optimal step
  time on each roof;
- :class:`CostLedger` persists rows to an **append-only JSON-lines ledger**
  (one row per line, corrupt lines skipped on read) keyed by the trainer's
  ``aot_key`` and the executable's StableHLO digest — the same fingerprint
  ``aot_save``/``aot_load`` trust, so a ledger row provably describes a
  specific compiled program;
- :func:`capture` is the one-call tap the trainer uses at compile time:
  lowered computation in, analyzed + persisted row out.

The ledger is the feature store the ROADMAP-1 autotuner reads ("A Learned
Performance Model for TPUs" builds its feature vectors from exactly these
per-program cost fields), and ``tools/perfwatch.py`` compares fresh rows
against cached bench baselines.

Everything here is host-side metadata extraction: with the ledger disabled
(``MXNET_PERF_LEDGER`` empty) nothing is lowered, written or counted, and
the jitted step's HLO is bitwise identical either way (tier-1 guards it).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ..analysis.lockwatch import make_lock
from ..base import get_env, logger, register_config
from . import metrics as _metrics

__all__ = ["DEVICE_PEAKS", "peak_flops", "peak_hbm_bw", "analyze_cost",
           "CostLedger", "ledger_path", "enabled", "get_ledger", "capture",
           "cost_of", "merge_costs", "memory_of"]

register_config("MXNET_PERF_LEDGER", "", str,
                "Path of the append-only JSON-lines cost ledger. Non-empty "
                "enables the perf layer's compile-time cost capture (one "
                "extra host-side lowering per executable, nothing in the "
                "compiled HLO); empty disables capture entirely.")
register_config("MXNET_PERF_PEAK_FLOPS", 0.0, float,
                "Per-chip peak FLOP/s override for roofline/MFU math. 0 = "
                "use the built-in device_kind table (required for devices "
                "the table does not know, e.g. the CPU backend).")
register_config("MXNET_PERF_PEAK_HBM_GBPS", 0.0, float,
                "Per-chip peak HBM bandwidth override in GB/s for the "
                "roofline ridge point. 0 = use the built-in table.")

# (device_kind substring, bf16 peak FLOP/s, HBM bytes/s) per chip — public
# TPU specs. Substring match, most-specific first ("v5 lite"/"v5e" before
# "v5"). The env overrides above win over the table.
DEVICE_PEAKS = (
    ("v6", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),
    ("v5", 459e12, 2765e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)


def _table_lookup(device_kind: Optional[str]):
    kind = (device_kind or "").lower()
    for sub, pf, bw in DEVICE_PEAKS:
        if sub in kind:
            return pf, bw
    return None, None


def peak_flops(device_kind: Optional[str]) -> Optional[float]:
    """Per-chip peak FLOP/s (env override wins over the table; None when
    neither knows the device)."""
    ov = float(get_env("MXNET_PERF_PEAK_FLOPS", 0.0))
    if ov > 0:
        return ov
    return _table_lookup(device_kind)[0]


def peak_hbm_bw(device_kind: Optional[str]) -> Optional[float]:
    """Per-chip peak HBM bandwidth in bytes/s (env override in GB/s wins)."""
    ov = float(get_env("MXNET_PERF_PEAK_HBM_GBPS", 0.0))
    if ov > 0:
        return ov * 1e9
    return _table_lookup(device_kind)[1]


def analyze_cost(cost: Dict[str, Any], device_kind: Optional[str] = None,
                 n_devices: int = 1) -> Dict[str, Any]:
    """Derive the roofline row from a raw ``cost_analysis()`` dict.

    Keys always present: ``flops``, ``bytes_accessed``, ``transcendentals``
    (None when XLA did not report them), ``arithmetic_intensity``,
    ``roofline`` (``compute-bound`` / ``memory-bound`` / ``unknown``),
    ``device_kind``, ``n_devices``. When the device's peaks are known
    (table or override) the row also carries ``peak_flops``,
    ``peak_hbm_bw``, ``ridge_intensity`` and the two roof times
    ``optimal_ms_compute`` / ``optimal_ms_memory`` — the step time a
    perfectly efficient execution would take on the compute or memory roof.
    """
    cost = cost or {}
    flops = float(cost.get("flops", 0.0) or 0.0) or None
    bytes_a = float(cost.get("bytes accessed", 0.0) or 0.0) or None
    trans = cost.get("transcendentals")
    row: Dict[str, Any] = {
        "flops": flops,
        "bytes_accessed": bytes_a,
        "transcendentals": float(trans) if trans else None,
        "device_kind": device_kind,
        "n_devices": int(n_devices),
    }
    intensity = (flops / bytes_a) if flops and bytes_a else None
    row["arithmetic_intensity"] = intensity
    pf = peak_flops(device_kind)
    bw = peak_hbm_bw(device_kind)
    if pf:
        row["peak_flops"] = pf
        if flops:
            row["optimal_ms_compute"] = flops / (pf * n_devices) * 1e3
    if bw:
        row["peak_hbm_bw"] = bw
        if bytes_a:
            row["optimal_ms_memory"] = bytes_a / (bw * n_devices) * 1e3
    ridge = (pf / bw) if pf and bw else None
    if ridge is not None:
        row["ridge_intensity"] = ridge
    if intensity is not None and ridge is not None:
        row["roofline"] = ("compute-bound" if intensity >= ridge
                           else "memory-bound")
    else:
        row["roofline"] = "unknown"
    return row


class CostLedger:
    """Append-only JSON-lines ledger of cost rows.

    One row per line keeps appends atomic enough for concurrent writers
    (single ``write`` of a short line in ``O_APPEND`` mode) and makes the
    file greppable/streamable; :meth:`rows` skips corrupt lines instead of
    failing, so a torn tail write can never poison the history.
    """

    def __init__(self, path: str):
        if not path:
            raise ValueError("CostLedger needs a path")
        self.path = str(path)
        self._lock = make_lock("observability.xcost.CostLedger._lock")

    def append(self, row: Dict[str, Any]) -> Dict[str, Any]:
        """Stamp and append one row; returns the stamped row."""
        row = dict(row)
        row.setdefault("version", 1)
        row.setdefault("time", time.time())
        row.setdefault("pid", os.getpid())
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        line = json.dumps(row, sort_keys=True, default=_json_default) + "\n"
        with self._lock, open(self.path, "a") as f:
            f.write(line)
        if _metrics.enabled():
            from . import catalog as _catalog
            _catalog.COST_LEDGER_ROWS.inc()
        return row

    def rows(self, fingerprint: Optional[str] = None) -> List[Dict[str, Any]]:
        """Every parseable row, oldest first (optionally filtered by
        executable fingerprint). A missing file is an empty ledger."""
        out: List[Dict[str, Any]] = []
        try:
            with open(self.path) as f:
                lines = f.readlines()
        except OSError:
            return out
        for ln in lines:
            ln = ln.strip()
            if not ln:
                continue
            try:
                row = json.loads(ln)
            except ValueError:
                continue                    # torn/corrupt line: skip, keep rest
            if isinstance(row, dict) and (
                    fingerprint is None
                    or row.get("fingerprint") == fingerprint):
                out.append(row)
        return out

    def last(self, fingerprint: Optional[str] = None) -> Optional[Dict[str, Any]]:
        rows = self.rows(fingerprint=fingerprint)
        return rows[-1] if rows else None

    def __len__(self) -> int:
        return len(self.rows())


def _json_default(o):
    try:
        return float(o)
    except Exception:
        return repr(o)


def ledger_path() -> str:
    return str(get_env("MXNET_PERF_LEDGER", "") or "")


def enabled() -> bool:
    """The cost-capture gate: a configured ledger path (and the telemetry
    master switch, checked by callers via ``metrics.enabled``)."""
    return bool(ledger_path())


def get_ledger() -> Optional[CostLedger]:
    path = ledger_path()
    return CostLedger(path) if path else None


def cost_of(lowered) -> Optional[Dict[str, Any]]:
    """Raw ``cost_analysis()`` dict of one lowered computation, or None
    when the backend reports nothing. Compile-free where supported — a
    compile is never triggered here."""
    try:
        ca = lowered.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return ca or None


def merge_costs(*costs) -> Optional[Dict[str, Any]]:
    """Sum the additive cost fields of several programs that together make
    one logical step (the kv path's grad + apply programs). ALL parts must
    be present — a partial sum would silently understate the step and
    poison every MFU computed from it."""
    if not costs or any(not c for c in costs):
        return None
    out: Dict[str, Any] = {}
    for ca in costs:
        for k in ("flops", "bytes accessed", "transcendentals"):
            v = ca.get(k)
            if v:
                out[k] = out.get(k, 0.0) + float(v)
    return out or None


def memory_of(compiled) -> Optional[Dict[str, int]]:
    """XLA ``memory_analysis()`` of one compiled executable as a plain
    byte dict, or None when the backend reports nothing. The shared
    extraction for every memory row (here and in ``memwatch``)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    return {
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "generated_code_bytes": int(
            getattr(mem, "generated_code_size_in_bytes", 0)),
    }


def capture(lowered=None, *, cost: Optional[Dict[str, Any]] = None,
            key: Optional[Dict[str, Any]] = None,
            fingerprint: Optional[str] = None, label: str = "",
            device_kind: Optional[str] = None, platform: Optional[str] = None,
            n_devices: int = 1, compiled=None, compile_for_memory: bool = False,
            extra: Optional[Dict[str, Any]] = None,
            ledger: Optional[CostLedger] = None) -> Optional[Dict[str, Any]]:
    """Analyze one logical step and persist the row.

    Pass ``lowered`` (a ``jax.stages.Lowered``) for a single-program step,
    or a precomputed ``cost`` dict (e.g. :func:`merge_costs` over the kv
    path's grad+apply programs) for multi-program steps. ``compiled`` may
    pass the already-compiled executable (the ``aot_save`` path) to enrich
    the row with XLA's memory analysis; ``compile_for_memory=True`` closes
    the lazy-path gap instead — an analysis compile of ``lowered`` is
    performed here solely for ``memory_analysis`` (the program actually
    dispatched is untouched; callers gate this on
    ``memwatch.capture_enabled()``). Returns the persisted row, or None
    when telemetry is off or the backend reports no costs. Never raises:
    the perf layer must not be able to kill training.
    """
    if not _metrics.enabled():
        return None
    try:
        ca = cost if cost is not None else cost_of(lowered)
        if not ca:
            logger.warning("cost ledger: backend reported no cost analysis "
                           "for %s", label or "executable")
            return None
        row = analyze_cost(ca, device_kind=device_kind, n_devices=n_devices)
        row.update({"label": label, "fingerprint": fingerprint,
                    "aot_key": key, "platform": platform})
        if compiled is not None:
            # only the aot_save-style path, where the compile just happened
            # inside this call, may claim the jit_hooks compile duration —
            # the lazy pre-dispatch step capture runs BEFORE its program
            # compiles, when last_compile_ms still names an earlier one
            from . import jit_hooks as _jit
            last_ms = _jit.last_compile_ms()
            if last_ms is not None:
                row["last_compile_ms"] = last_ms
        elif compile_for_memory and lowered is not None:
            try:
                compiled = lowered.compile()
            except Exception:
                compiled = None
        if compiled is not None:
            mem = memory_of(compiled)
            if mem:
                row["memory"] = mem
                row["peak_memory_bytes"] = (mem["temp_bytes"]
                                            + mem["argument_bytes"]
                                            + mem["output_bytes"])
        if extra:
            row.update(extra)
        led = ledger if ledger is not None else get_ledger()
        if led is not None:
            led.append(row)
        return row
    except Exception as e:  # pragma: no cover - defensive: never kill a run
        logger.warning("cost ledger capture failed: %r", e)
        return None
