"""Profiler — chrome-trace JSON + XLA/TPU trace sessions.

Reference parity: ``src/profiler/profiler.{h,cc}`` + ``python/mxnet/profiler.py``
(set_config/start/stop/dump, mode bitmask {symbolic, imperative, api, memory}
profiler.h:256-262, ProfileDomain/Task/Event/Counter/Marker objects
profiler.h:556+, aggregate summary aggregate_stats.cc, env autostart
MXNET_PROFILER_AUTOSTART).

TPU-first: host-side events (op dispatches, graph executions, API calls) are
recorded directly in chrome-trace format; device-side timing comes from an
XLA profiler session (``jax.profiler``) whose TensorBoard trace dir sits next
to the JSON file — the split mirrors the reference's CPU-op vs GPU-kernel
event streams.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

from .base import get_env

__all__ = ["set_config", "start", "stop", "pause", "resume", "dump", "dumps",
           "Domain", "Task", "Event", "Counter", "Marker", "profiler_set_state",
           "set_state", "set_kvstore_handle"]

_lock = threading.Lock()


class _ProfilerState:
    def __init__(self):
        self.running = False
        # pause depth, not a flag: pause()/resume() nest (refcounted), so a
        # library span that brackets its own pause/resume can never un-pause
        # a user's outer pause (reference profiler.cc pause counter)
        self.pause_depth = 0
        self.events: List[dict] = []
        self.filename = "profile.json"
        self.modes = {"symbolic": True, "imperative": True, "api": False,
                      "memory": False}
        self.aggregate = False
        self.xla_trace_dir: Optional[str] = None
        self.t0 = time.perf_counter()
        self.anchor_us: Optional[float] = None   # _ANCHOR's start, see start()

    def us(self):
        return (time.perf_counter() - self.t0) * 1e6


_prof = _ProfilerState()
# the span both streams hold: start() emits it into the XLA trace and notes
# its time on this profiler's clock, so the merge can put the device lanes
# on the host events' clock
_ANCHOR = "mx.profiler.anchor"


# ---- server-process profiling over the kvstore control channel -----------
# Reference: profiler commands ride the ps-lite control wire to server nodes
# (KVStoreServerProfilerCommand, include/mxnet/kvstore.h:49; exercised by
# tests/nightly/test_server_profiling.py). TPU-native: "servers" are every
# rank's in-process store shard; commands broadcast through the coordination
# service (kvstore._send_command_to_servers) and each rank applies them to
# its server-role profile state below.

profiler_kvstore_handle = None

# the server role shares the process-wide event stream but owns its state:
# config/run/pause arriving on the control channel never clobber what the
# local worker-side profiler is doing
_server = {"filename": "server_profile.json", "running": False,
           "paused": False, "started_engine": False}


def set_kvstore_handle(kvstore) -> None:
    """Register the kvstore whose control channel carries
    profile_process='server' commands (reference profiler.py:29)."""
    global profiler_kvstore_handle
    profiler_kvstore_handle = kvstore


def _send_server_cmd(head: int, body: str) -> None:
    from .base import MXNetError
    if profiler_kvstore_handle is None:
        raise MXNetError(
            "profile_process='server' needs a dist kvstore registered via "
            "profiler.set_kvstore_handle(kv)")
    profiler_kvstore_handle._send_command_to_servers(head, body)


def _server_set_config(body: str, rank: int) -> None:
    cfg = json.loads(body)
    with _lock:
        fname = cfg.get("filename")
        if fname:
            _server["filename"] = "rank%d_%s" % (rank, fname)


def _server_set_state(body: str) -> None:
    st = json.loads(body).get("state", "stop")
    if st == "run":
        _server["running"] = True
        if not _prof.running:           # share the process event stream
            start()
            _server["started_engine"] = True
    else:
        _server["running"] = False
        if _server["started_engine"]:
            stop()
            _server["started_engine"] = False


def _server_pause(body: str) -> None:
    _server["paused"] = bool(json.loads(body).get("paused", True))


def _server_dump(rank: int) -> None:
    with _lock:
        trace = {"traceEvents": list(_prof.events), "displayTimeUnit": "ms"}
    with open(_server["filename"], "w") as f:
        json.dump(trace, f)


def set_config(profile_all=False, profile_symbolic=False, profile_imperative=False,
               profile_memory=False, profile_api=False, filename="profile.json",
               aggregate_stats=False, profile_process="worker",
               xla_trace_dir=None, **kwargs):
    if profile_process == "server":
        from .kvstore import CMD_SET_PROFILER_CONFIG
        _send_server_cmd(CMD_SET_PROFILER_CONFIG,
                         json.dumps({"filename": filename,
                                     "profile_all": bool(profile_all)}))
        return
    with _lock:
        _prof.filename = filename
        _prof.aggregate = aggregate_stats
        _prof.xla_trace_dir = xla_trace_dir
        if profile_all:
            for k in _prof.modes:
                _prof.modes[k] = True
        else:
            _prof.modes.update(symbolic=profile_symbolic,
                               imperative=profile_imperative,
                               memory=profile_memory, api=profile_api)


def start():
    with _lock:
        _prof.running = True
        _prof.pause_depth = 0
        _prof.t0 = time.perf_counter()
        _prof.events = []
    if _prof.xla_trace_dir:
        import jax
        try:
            # device/XLA lanes only — the python tracer adds tens of
            # thousands of interpreter-frame events we don't want merged
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(_prof.xla_trace_dir,
                                     profiler_options=opts)
        except Exception:
            jax.profiler.start_trace(_prof.xla_trace_dir)
        with jax.profiler.TraceAnnotation(_ANCHOR):
            _prof.anchor_us = _prof.us()


def stop():
    with _lock:
        _prof.running = False
    if _prof.xla_trace_dir:
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        n = _merge_xla_trace(_prof.xla_trace_dir)
        if n:
            record_event("xla_device_trace_merged", "profiler", _prof.us(),
                         0.0, {"events": n})


def _merge_xla_trace(trace_dir: str) -> int:
    """Fold the XLA profiler's own chrome trace (device lanes: per-op XLA
    timings, TPU steps) into our event list so ``dump()`` emits ONE trace
    with host + device rows — the reference's engine ``opr_profile`` gives
    the same merged view (src/profiler/profiler.h:556).

    jax.profiler.stop_trace writes plugins/profile/<run>/<host>.trace.json.gz
    (TensorBoard layout); we take the newest run, shift its timestamps so
    that the anchor span ``start()`` emitted lands where this profiler's own
    clock saw it (host spans and device lanes then share one clock), and
    keep its pid/tid lane metadata. A trace without the anchor (host tracer
    off) falls back to putting its first event at zero."""
    import glob
    import gzip
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
    if not paths:
        return 0
    latest = max(paths, key=os.path.getmtime)
    try:
        with gzip.open(latest, "rt") as f:
            data = json.load(f)
    except Exception:
        return 0
    evs = data.get("traceEvents") or []
    stamped = [e for e in evs if isinstance(e.get("ts"), (int, float))
               and e.get("ph") != "M"]
    if not stamped:
        return 0
    anchor = [e["ts"] for e in stamped if e.get("name") == _ANCHOR]
    if anchor and _prof.anchor_us is not None:
        # the annotation opens a moment before anchor_us is read inside it
        shift = anchor[0] - _prof.anchor_us
    else:
        shift = min(e["ts"] for e in stamped)
    merged = 0
    with _lock:
        for e in evs:
            e = dict(e)
            if str(e.get("name", "")).startswith("$"):
                continue        # python-tracer interpreter frames
            # device lanes keep their own pid; offset into our pid space so
            # they can never collide with the host process row
            if isinstance(e.get("pid"), int):
                e["pid"] = e["pid"] + (1 << 20)
            if isinstance(e.get("ts"), (int, float)) and e.get("ph") != "M":
                e["ts"] = e["ts"] - shift
            e.setdefault("args", {})
            if e.get("ph") != "M":
                e["args"]["lane"] = "xla-device"
            _prof.events.append(e)
            merged += 1
    return merged


def pause(profile_process="worker"):
    """Suspend event recording. Nestable: each ``pause()`` must be matched
    by one ``resume()`` — recording restarts only when the depth returns to
    zero, so instrumentation bracketing its own pause/resume cannot
    un-pause an enclosing user pause."""
    if profile_process == "server":
        from .kvstore import CMD_PROFILER_PAUSE
        return _send_server_cmd(CMD_PROFILER_PAUSE,
                                json.dumps({"paused": True}))
    with _lock:
        _prof.pause_depth += 1


def resume(profile_process="worker"):
    """Undo one ``pause()`` (refcounted; extra resumes are no-ops)."""
    if profile_process == "server":
        from .kvstore import CMD_PROFILER_PAUSE
        return _send_server_cmd(CMD_PROFILER_PAUSE,
                                json.dumps({"paused": False}))
    with _lock:
        _prof.pause_depth = max(0, _prof.pause_depth - 1)


def profiler_set_state(state="stop"):
    if state == "run":
        start()
    else:
        stop()


def set_state(state="stop", profile_process="worker"):
    """Reference mx.profiler.set_state: run/stop the worker profiler, or —
    with profile_process='server' — every server role over the kvstore
    control channel (tests/nightly/test_server_profiling.py)."""
    if profile_process == "server":
        from .kvstore import CMD_SET_PROFILER_STATE
        return _send_server_cmd(CMD_SET_PROFILER_STATE,
                                json.dumps({"state": state}))
    profiler_set_state(state)


def is_active(kind: str = "imperative") -> bool:
    return _prof.running and _prof.pause_depth == 0 \
        and _prof.modes.get(kind, False)


def recording() -> bool:
    """True while a worker profiling session is running and not paused —
    the gate observability spans use to mirror themselves into the
    chrome-trace stream regardless of mode bits."""
    return _prof.running and _prof.pause_depth == 0


def record_event(name: str, category: str, t_start_us: float, dur_us: float,
                 args: Optional[dict] = None):
    with _lock:
        _prof.events.append({
            "name": name, "cat": category, "ph": "X",
            "ts": t_start_us, "dur": dur_us,
            "pid": os.getpid(), "tid": threading.get_ident() % (1 << 31),
            "args": args or {}})


class _Scope:
    def __init__(self, name, category):
        self.name = name
        self.category = category

    def __enter__(self):
        self.start = _prof.us()
        return self

    def __exit__(self, *exc):
        record_event(self.name, self.category, self.start,
                     _prof.us() - self.start)
        return False


def scope(name: str, category: str = "operator") -> _Scope:
    return _Scope(name, category)


def _aggregate_table(events) -> str:
    """Per-name count/total/mean/max table (reference aggregate_stats.cc
    ``DumpTable``), sorted by total descending."""
    agg: Dict[str, List[float]] = defaultdict(list)
    for e in events:
        name, dur = e.get("name"), e.get("dur")
        if name is None or dur is None:  # metadata / phase-less rows
            continue
        agg[name].append(dur)
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(us)':>14}{'Mean(us)':>12}"
             f"{'Max(us)':>12}"]
    for name, durs in sorted(agg.items(), key=lambda kv: -sum(kv[1])):
        lines.append(f"{name:<40}{len(durs):>8}{sum(durs):>14.1f}"
                     f"{sum(durs)/len(durs):>12.1f}{max(durs):>12.1f}")
    return "\n".join(lines)


def dumps(reset=False) -> str:
    """Aggregate text summary (reference aggregate_stats.cc table)."""
    with _lock:
        events = list(_prof.events)
        if reset:
            _prof.events = []
    return _aggregate_table(events)


def dump(finished=True, profile_process="worker"):
    """Write the chrome trace JSON (load in chrome://tracing / Perfetto).

    When the session was configured with ``aggregate_stats=True``, also
    write the aggregate summary table (count/total/mean/max per name —
    reference aggregate_stats.cc) to ``<filename>.aggregate.txt``."""
    if profile_process == "server":
        from .kvstore import CMD_PROFILER_DUMP
        return _send_server_cmd(CMD_PROFILER_DUMP, "")
    with _lock:
        events = list(_prof.events)
        trace = {"traceEvents": events, "displayTimeUnit": "ms"}
        with open(_prof.filename, "w") as f:
            json.dump(trace, f)
        aggregate, filename = _prof.aggregate, _prof.filename
        if finished:
            _prof.events = []
    if aggregate:
        with open(filename + ".aggregate.txt", "w") as f:
            f.write(_aggregate_table(events) + "\n")


# ---- user-facing objects (reference profiler.py:Domain/Task/Event/...) ----
class Domain:
    def __init__(self, name):
        self.name = name


class Task:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._start = None

    def start(self):
        self._start = _prof.us()

    def stop(self):
        if self._start is not None:
            record_event(self.name, self.domain.name, self._start,
                         _prof.us() - self._start)
            self._start = None


class Event(Task):
    pass


class Counter:
    def __init__(self, domain, name, value=0):
        self.domain = domain
        self.name = name
        self.value = value
        self._emit()

    def _emit(self):
        with _lock:
            _prof.events.append({"name": self.name, "cat": self.domain.name,
                                 "ph": "C", "ts": _prof.us(),
                                 "pid": os.getpid(),
                                 "args": {"value": self.value}})

    def set_value(self, value):
        self.value = value
        self._emit()

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)

    __iadd__ = increment
    __isub__ = decrement


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope_="process"):
        with _lock:
            _prof.events.append({"name": self.name, "cat": self.domain.name,
                                 "ph": "i", "ts": _prof.us(), "s": "p",
                                 "pid": os.getpid()})


# reference back-compat alias (python/mxnet/profiler.py dump_profile)
dump_profile = dump

if get_env("MXNET_PROFILER_AUTOSTART", False):
    set_config(profile_all=True)
    start()
