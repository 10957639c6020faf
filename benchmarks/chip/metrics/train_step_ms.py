"""Device time of one training step: the summed device time of the step
program's runs (module ``jit_step``) in the traced window, over their number."""

MODULE = "jit_step"


def read(r):
    runs, seconds = r.trace.modules(MODULE)
    return 1e3 * seconds / runs if runs else None
