"""Mean time a step waited for its batch: the harness span around
``next(feed)`` in the measured window."""


def read(run):
    s = run["spans"].get("feed.next")
    return 1e3 * sum(s) / len(s) if s else None
