"""What the host itself does per step: the median over the window of the
program's span ``trainer.step`` less its ``trainer.enqueue`` (unwrap, batch
``device_put``, the key programs, the step's bookkeeping)."""
import statistics

from chipbench import program_spans


def read(run):
    s = [st["trainer.step"] - st["trainer.enqueue"]
         for st in program_spans.select(run)["steps"] if "trainer.enqueue" in st]
    return 1e3 * statistics.median(s) if s else None
