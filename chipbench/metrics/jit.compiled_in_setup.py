"""Executables set-up really compiled: the program's ``jit.compile`` records
of set-up that enclose no ``jit.cache_load`` of their thread (a load from
the persistent cache fires both). A warm start reads 0; None on a program
that does not record cache loads."""
from chipbench import setup_spans


def read(run):
    try:
        from mxnet_tpu.observability import jit_hooks
    except ImportError:
        return None
    s = setup_spans.select(run)
    if s is None or not hasattr(jit_hooks, "_CACHE_LOAD_EVENT"):
        return None
    recs = [r for r in s["records"] if s["begin"] <= r.t0 and r.t1 <= s["end"]]
    loads = [r for r in recs if r.name == "jit.cache_load"]
    return sum(not any(c.t0 <= ld.t1 <= c.t1 and ld.thread == c.thread
                       for ld in loads)
               for c in recs if c.name == "jit.compile")
