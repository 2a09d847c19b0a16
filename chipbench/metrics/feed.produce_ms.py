"""Mean time the feed's producer works per batch of the window: the program's
spans ``feed.base_next`` (the base iterator) plus ``feed.stage`` (cast,
``device_put``, rescale issue). Against the step's time it is the feed's
headroom."""
from chipbench import program_spans


def read(run):
    s = [b["feed.base_next"] + b["feed.stage"]
         for b in program_spans.select(run)["batches"]
         if "feed.base_next" in b and "feed.stage" in b]
    return 1e3 * sum(s) / len(s) if s else None
