"""Set-up as the program's own span record covers it, for the ``setup.*``
readers and ``jit.compiled_in_setup`` under ``metrics/``.

Set-up runs from the start of the process to the ``t0`` of the measured
window's first ``trainer.step``, found by count as ``program_spans.select``
finds the window (``select`` repeats that search: the readers here need the
records, where ``program_spans.select`` returns sums). Where an earlier trainer stepped in the same process (the
tests), it runs from where that trainer stopped stepping instead. The start of
the process is the OS's record (``/proc/self/stat``), on the clock of
``time.perf_counter``. The clock of ``run.py``'s ``setup_s`` starts later, at
its first line, and stops just before the window does.

Where the ring has pushed a record out, or holds no window (telemetry off),
``select`` gives None and the readers leave their metric out.

To split one run's set-up by the stages the harness names on stderr:

    python3 chipbench/setup_spans.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs the cell as ``run.py`` does, prints its result line, and then one more
JSON line: set-up's interval, the seconds the program's records cover in it,
and for each stage its seconds and the part no record covers.
"""
import os
import re
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def process_start():
    """``perf_counter`` seconds at which the OS started this process, or None
    where ``/proc`` cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
    except OSError:
        return None
    # field 2, the command's name, may hold spaces: count from its ")"
    ticks = int(stat[stat.rindex(")") + 2:].split()[19])    # field 22
    boot_less_clock = time.clock_gettime(time.CLOCK_BOOTTIME) - time.perf_counter()
    return ticks / os.sysconf("SC_CLK_TCK") - boot_less_clock


def _program():
    """The program's span module, or None without one."""
    try:
        from mxnet_tpu.observability import spans
        return spans
    except ImportError:
        return None


def _has_dropped(spans, recs):
    if hasattr(spans, "dropped"):
        return spans.dropped() > 0
    return len(recs) >= spans.RING_RECORDS    # a program without the count


def select(run):
    """``{"begin", "end", "thread", "records"}``: the earliest moment set-up
    may start (``-inf`` in a process that stepped no trainer before), the
    window's first ``trainer.step``'s ``t0`` and thread, and every record of
    the ring. None where the ring has dropped a record or does not hold the
    window."""
    spans = _program()
    if spans is None:
        return None
    recs = spans.records()
    n = len(run["spans"].get("trainer.step") or ())
    if not recs or not n or _has_dropped(spans, recs):
        return None
    skip = run["mix"]["followed_steps"] + run["mix"]["warmup_steps"]
    roots = [r for r in recs if r.name == "trainer.step"]
    ones = [i for i, r in enumerate(roots) if r.unit == ("step", 1)]
    if not ones:
        return None
    win = roots[ones[-1]:][skip:skip + n]
    if len(win) < n or any(r.t1 - r.t0 > h for r, h in
                           zip(win, run["spans"]["trainer.step"])):
        return None
    begin = roots[ones[-1] - 1].t1 if ones[-1] else float("-inf")
    return {"begin": begin, "end": win[0].t0, "thread": win[0].thread,
            "records": recs}


def covered(recs, a, b):
    """Seconds of [a, b] that the union of the records' intervals covers."""
    total, reach = 0.0, a
    for r in sorted(recs, key=lambda r: r.t0):
        lo, hi = max(r.t0, reach), min(r.t1, b)
        if hi > lo:
            total += hi - lo
            reach = hi
    return total


def named(run, names):
    """Seconds of set-up that the records of these names cover; None where
    the ring holds none of them at all (a program without the span)."""
    s = select(run)
    if s is None:
        return None
    recs = [r for r in s["records"] if r.name in names]
    if not recs:
        return None
    return covered(recs, s["begin"], s["end"])


def interval(run):
    """``(start, end, records on the window's thread)`` of set-up, the start
    the later of the process's start and ``begin``; None where ``select`` or
    ``process_start`` gives none."""
    s, start = select(run), process_start()
    if s is None or start is None:
        return None
    own = [r for r in s["records"] if r.thread == s["thread"]]
    return max(start, s["begin"]), s["end"], own


def main(argv):
    import json
    from chipbench import harness, run as run_py

    stages, seen = [], {}
    say, find_cell, drive = harness.stderr, harness.find_cell, harness.drive

    def stderr(*a):
        stages.append((time.perf_counter(), " ".join(map(str, a))))
        say(*a)

    def find(*a, **k):
        seen["cell"] = find_cell(*a, **k)
        return seen["cell"]

    def window(trainer, feed, seconds, in_flight, spans):
        seen.setdefault("spans", spans)     # the first drive is the window
        return drive(trainer, feed, seconds, in_flight, spans)

    harness.stderr, harness.find_cell, harness.drive = stderr, find, window
    result = run_py.run(argv)
    got = interval({"spans": seen["spans"].seconds, "mix": seen["cell"][2]})
    if got is None:
        raise SystemExit("chipbench: the span record does not hold set-up")
    start, end, own = got
    marks = [(start, "process start")] + [
        (t, m.group(1)) for t, m in
        ((t, re.match(r"chipbench: +[\d.]+ s  (.*)", line)) for t, line in stages)
        if m and t < end] + [(end, "first timed step")]
    spans = _program()
    recs = spans.records()
    print(json.dumps({
        "interval_s": end - start, "covered_s": covered(own, start, end),
        "setup_s": result["info"]["setup_s"],
        "before_run_py_s": run_py.T_START - start,
        "after_setup_s_s": end - run_py.T_START - result["info"]["setup_s"],
        "stages": [{"from": a[1], "to": b[1], "s": b[0] - a[0],
                    "unnamed_s": b[0] - a[0] - covered(own, a[0], b[0])}
                   for a, b in zip(marks, marks[1:])],
        "by_name_s": {n: covered([r for r in own if r.name == n], start, end)
                      for n in sorted({r.name for r in own})},
        "setup_records": sum(r.t1 <= end for r in recs),
        "records": len(recs),
        "dropped": spans.dropped() if hasattr(spans, "dropped") else None}),
        flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
