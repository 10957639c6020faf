"""Share of the held experts' assignments dropped over capacity: the
program's ``train.moe`` counters (``dropped`` over ``routed``), summed over
the window's steps."""
from chiplib.program_spans import in_window


def read(r):
    steps = [s.attrs for s in in_window(r, "train.moe")]
    routed = sum(a["routed"] for a in steps)
    return 100.0 * sum(a["dropped"] for a in steps) / routed if routed else None
