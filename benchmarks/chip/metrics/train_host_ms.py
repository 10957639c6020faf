"""Host time the training loop spends on a step before the step runs: the
batch (``train.batch``), its copy to the device (``train.h2d``) and the
step's dispatch (``train.dispatch``), summed per step, the median over the
window's steps that have all three.  The median, because the benchmark's
own work lands inside one step's spans now and then (the profiler's trace,
stopped from the feed after the traced steps, takes seconds)."""
import statistics

from chiplib.program_spans import in_window

NAMES = ("train.batch", "train.h2d", "train.dispatch")


def read(r):
    steps = {}
    for s in in_window(r, *NAMES):
        steps.setdefault(s.attrs.get("step"), {})[s.name] = s.seconds
    whole = [sum(p.values()) for p in steps.values() if len(p) == len(NAMES)]
    return 1e3 * statistics.median(whole) if whole else None
