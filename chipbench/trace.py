"""Reduce a JAX profiler trace (``*.xplane.pb``) to device numbers.

What a TPU v5e trace holds (looked at by hand, PERF.md finding 23.3): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per program run, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event
per HLO op run; the name is the op's HLO text), ``Async XLA Ops`` (the spans
of copy-start/-done and the collectives' start/done pairs) and ``Steps``.
The per-op events carry only a time; ``hlo_category`` ("convolution fusion",
"loop fusion", "data formatting", ...) sits in the plane's event METADATA,
which ``jax.profiler.ProfileData`` does not show. So this file reads the
protobuf wire format itself (the schema is five small messages) and needs
nothing but the standard library. Host threads are lines of ``/host:CPU``;
``jax.profiler.TraceAnnotation`` spans appear there under their own names.
All times are picoseconds on one clock.
"""
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
# TPU XLA turns dots into convolutions: both land in these categories.
MATMUL_CATEGORIES = ("convolution", "convolution fusion")
# A Pallas kernel is a custom call. Each counts as products unless its name
# (as ``ops/pallas_kernels.py`` gives it, behind JAX's ``jvp_`` where the
# kernel is differentiated) begins with one of these kernels, which do no
# products: a new one that does none is added here by a ``benchmark`` PR.
CUSTOM_CALL = "custom-call"
BANDWIDTH_KERNELS = ("max_pool_fwd", "max_pool_bwd", "cross_entropy_lse")
# XLA's own custom calls (``X64SplitLow``, ``X64SplitHigh``, ``X64Combine``
# in every cell's trace) name another target in the event's HLO text: no
# kernel, no products. A text that names no target counts as a kernel.
TARGET = "custom_call_target="
PALLAS_TARGET = TARGET + '"tpu_custom_call"'
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


# ------------------------------------------------------------ wire format
def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b):
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 1:
            v = b[i:i + 8]
            i += 8
        elif wt == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError("wire type %d" % wt)
        yield f, v


def _map_entry(b):
    key = val = None
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(b, want_events):
    """{name, lines: {line name: [(start_ps, dur_ps, metadata id)]},
    meta: {id: (name, category)}}"""
    name, lines_raw, emeta_raw, smeta = "", [], {}, {}
    for f, v in _fields(b):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines_raw.append(v)
        elif f == 4:
            k, val = _map_entry(v)
            emeta_raw[k] = val
        elif f == 5:
            k, val = _map_entry(v)
            for mf, mv in _fields(val):
                if mf == 2:
                    smeta[k] = bytes(mv).decode()
    out = {"name": name, "lines": {}, "meta": {}}
    if not want_events(name):
        return out
    cat_id = [k for k, v in smeta.items() if v == "hlo_category"]
    for k, raw in emeta_raw.items():
        nm, cat = "", None
        for f, v in _fields(raw):
            if f == 2:
                nm = bytes(v).decode(errors="replace")
            elif f == 5 and cat_id:
                sid = sval = None
                for sf, sv in _fields(v):
                    if sf == 1:
                        sid = sv
                    elif sf == 5:
                        sval = bytes(sv).decode(errors="replace")
                    elif sf == 7:
                        sval = smeta.get(sv)
                if sid == cat_id[0]:
                    cat = sval
        out["meta"][k] = (nm, cat)
    for raw in lines_raw:
        lname, t0_ns, events = "", 0, []
        for f, v in _fields(raw):
            if f == 2:
                lname = bytes(v).decode()
            elif f == 3:
                t0_ns = v
            elif f == 4:
                mid = off = dur = 0
                for ef, ev in _fields(v):
                    if ef == 1:
                        mid = ev
                    elif ef == 2:
                        off = ev
                    elif ef == 3:
                        dur = ev
                events.append((off, dur, mid))
        base = t0_ns * 1000
        out["lines"].setdefault(lname, []).extend(
            (base + off, dur, mid) for off, dur, mid in events)
    return out


def read(path):
    """The device planes and the host plane of one xplane file."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    want = lambda n: bool(DEVICE_PLANE.match(n)) or n == HOST_PLANE  # noqa: E731
    planes = [_plane(v, want) for f, v in _fields(data) if f == 1]
    return [p for p in planes if want(p["name"])]


# ------------------------------------------------------------- intervals
def union(intervals):
    """Merged, sorted [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """Each event's duration less the union of the events of the same line
    that it encloses: a ``while``, ``conditional`` or ``call`` holds the ops
    of its body, and each of those counts once, by itself. Of two events
    that overlap without one enclosing the other, each keeps its whole
    duration; of two with the same interval, the first encloses the second."""
    own = [d for _s, d, _m in events]
    inner, open_ = {}, []   # enclosing event -> enclosed intervals; a chain
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i][0], -events[i][1])):
        s, d, _m = events[i]
        while open_ and open_[-1][1] < s + d:
            open_.pop()
        if open_:
            inner.setdefault(open_[-1][0], []).append((s, s + d))
        open_.append((i, s + d))
    for i, intervals in inner.items():
        own[i] -= total(union(intervals))
    return own


# ------------------------------------------------------------- reduction
def _short(hlo_name):
    return hlo_name.split(" = ")[0].lstrip("%")[:64]


def is_product(hlo_text, category):
    """Whether an event does the step's products: XLA's convolution
    categories (fused epilogues included) and every Pallas kernel but the
    bandwidth kernels."""
    if category in MATMUL_CATEGORIES:
        return True
    if category != CUSTOM_CALL or (TARGET in hlo_text
                                   and PALLAS_TARGET not in hlo_text):
        return False
    name = _short(hlo_text)
    return not (name[4:] if name.startswith("jvp_") else name).startswith(
        BANDWIDTH_KERNELS)


def reduce(planes, step_module="jit_train_step", spans=()):
    """Device numbers of one traced window.

    Per device: busy_ps (union of its op intervals), per-step time by
    category for the runs of ``step_module`` (each event by its self time,
    so the categories sum to no more than busy_ps), exposed collective time.
    ``matmul_s_per_step`` is the products' time (``is_product``),
    ``other_s_per_step`` every other non-collective event's.
    ``spans`` names host annotations (the program's spans); each long idle
    gap is attributed to those open at its midpoint.
    """
    devices, host = [], None
    for p in planes:
        if p["name"] == HOST_PLANE:
            host = p
            continue
        meta = p["meta"]
        ops = p["lines"].get("XLA Ops", [])
        mods = [e for e in p["lines"].get("XLA Modules", [])
                if meta.get(e[2], ("",))[0].startswith(step_module)]
        busy = union((s, s + d) for s, d, _ in ops)
        by_cat, by_op, coll, other, products = {}, {}, [], [], 0
        for (s, d, mid), own in zip(ops, self_times(ops)):
            nm, cat = meta.get(mid, ("", None))
            if COLLECTIVE.search(nm) or COLLECTIVE.search(cat or ""):
                cat = "collective"
                coll.append((s, s + d))
            else:
                other.append((s, s + d))
            cat = cat or "uncategorised"
            by_cat[cat] = by_cat.get(cat, 0) + own
            key = (_short(nm), cat)
            by_op[key] = by_op.get(key, 0) + own
            if is_product(nm, cat):
                products += own
        # the collectives' asynchronous spans: start to done
        for s, d, mid in p["lines"].get("Async XLA Ops", []):
            if COLLECTIVE.search(meta.get(mid, ("",))[0]):
                coll.append((s, s + d))
        exposed = subtract(union(coll), union(other))
        devices.append({
            "name": p["name"], "steps": len(mods),
            "step_ps": sorted(d for _, d, _ in mods),
            "busy": busy, "busy_ps": total(busy), "by_cat": by_cat,
            "by_op": by_op, "products_ps": products,
            "collective_ps": total(union(coll)),
            "exposed_collective_ps": total(exposed)})
    if not devices:
        return None
    out = {"devices": devices, "n_devices": len(devices)}
    fullest = max(devices, key=lambda d: d["busy_ps"])
    steps = max(fullest["steps"], 1)
    out["steps"] = fullest["steps"]
    out["busy_s"] = sum(d["busy_ps"] for d in devices) / len(devices) / 1e12
    out["busy_fullest_s"] = fullest["busy_ps"] / 1e12
    out["matmul_s_per_step"] = fullest["products_ps"] / steps / 1e12
    out["other_s_per_step"] = (sum(v for k, v in fullest["by_cat"].items()
                                   if k != "collective")
                               - fullest["products_ps"]) / steps / 1e12
    out["exposed_collective_s_per_step"] = max(
        d["exposed_collective_ps"] / max(d["steps"], 1) for d in devices) / 1e12
    out["collective_s_per_step"] = max(
        d["collective_ps"] / max(d["steps"], 1) for d in devices) / 1e12
    top = sorted(fullest["by_op"].items(), key=lambda kv: -kv[1])[:10]
    out["device_ops"] = [["%s [%s]" % k, v / 1e12] for k, v in top]
    out["idle_gaps"] = _gaps(fullest["busy"], host, spans)
    return out


def _gaps(busy, host, spans):
    """The ten longest idle gaps between the first and the last op, each
    named by the spans of ``spans`` open on the host at its midpoint."""
    open_spans = []
    if host is not None:
        for evs in host["lines"].values():
            for s, d, mid in evs:
                # a step annotation may carry its number behind a '#'
                nm = host["meta"].get(mid, ("",))[0].split("#", 1)[0]
                if nm in spans:
                    open_spans.append((s, s + d, nm))
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:])), reverse=True)[:10]
    out = []
    for length, s, e in gaps:
        mid = (s + e) // 2
        names = sorted({nm for a, b, nm in open_spans if a <= mid < b})
        out.append(["+".join(names) or "no span open", length / 1e12])
    return out
