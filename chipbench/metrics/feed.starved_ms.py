"""Mean time the step was starved: the program's span ``feed.get_wait``
(``DeviceFeedIter.next`` blocked on an empty queue) over the window's
batches."""
from chipbench import program_spans


def read(run):
    s = [b["feed.get_wait"] for b in program_spans.select(run)["batches"]]
    return 1e3 * sum(s) / len(s) if s else None
