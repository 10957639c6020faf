"""Share of the traced window in which no operation ran on the device."""


def read(r):
    return 100.0 * r.trace.idle_share if r.trace.busy_s > 0 else None
