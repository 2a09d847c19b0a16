"""The measured window's part of the program's own span record
(``mxnet_tpu.observability.spans.records()``), for the ``program_span``
readers under ``metrics/``.

The harness keeps durations only, so the window is found by count: before it
the process calls ``trainer.step`` exactly ``followed_steps + warmup_steps``
times (``run.py``, ``harness.follow_program``) and inside it
``len(run["spans"]["trainer.step"])`` times (``harness.drive``), and
``feed.next`` the same. Set-up is everything that ends before the window's
first ``trainer.step`` begins; the traced tail and the reference's compiles
come after the window and are thereby left out.

A program without the record (the parent of the PR that brought it), or with
telemetry off, gives EMPTY, and the readers leave their metric out.
"""

EMPTY = {"steps": [], "batches": [], "setup": {}}


def _records():
    try:
        from mxnet_tpu.observability import spans
        return spans.records()
    except (ImportError, AttributeError):
        return None


def _seconds(recs):
    out = {}
    for r in recs:
        out[r.name] = out.get(r.name, 0.0) + (r.t1 - r.t0)
    return out


def select(run):
    """``{"steps": [...], "batches": [...], "setup": {...}}``: for each step
    of the window and for each batch it took from the feed a ``{span name:
    seconds}`` of the spans that share its ``unit``, and the same summed over
    set-up. EMPTY where the record does not hold the window."""
    recs = _records()
    n = len(run["spans"].get("trainer.step") or ())
    if not recs or not n:
        return EMPTY
    skip = run["mix"]["followed_steps"] + run["mix"]["warmup_steps"]
    roots = [r for r in recs if r.name == "trainer.step"]
    # the run's trainer is the last one that made its step 1 (the tests run
    # several in one process; for them set-up begins where the one before
    # stopped stepping)
    ones = [i for i, r in enumerate(roots) if r.unit == ("step", 1)]
    if not ones:
        return EMPTY
    begin = roots[ones[-1] - 1].t1 if ones[-1] else float("-inf")
    win = roots[ones[-1]:][skip:skip + n]
    # the program's root lies inside the harness span of the same index, or
    # the counts do not line up (a record left by an earlier run)
    if len(win) < n or any(r.t1 - r.t0 > h for r, h in
                           zip(win, run["spans"]["trainer.step"])):
        return EMPTY
    recs = [r for r in recs if r.t0 >= begin]
    by_unit = {}
    for r in recs:
        by_unit.setdefault(r.unit, []).append(r)
    steps = [_seconds(r for r in by_unit[root.unit]
                      if root.t0 <= r.t0 and r.t1 <= root.t1
                      and r.thread == root.thread) for root in win]
    gets = [r for r in recs if r.name == "feed.get_wait"][skip:skip + n]
    batches = []
    for g in gets:
        # an earlier feed of the process numbered its batches alike: the
        # batch's own spans are the last of each name
        last = {r.name: r for r in by_unit[g.unit] if r.name.startswith("feed.")}
        batches.append(_seconds(last.values()))
    return {"steps": steps, "batches": batches,
            "setup": _seconds(r for r in recs if r.t1 <= win[0].t0)}
