"""Files of a replica in flight at once: the seconds of the replication's
file copies and verifications (program spans ``transport.copy`` and
``transport.verify``) in the window, over the seconds of the
``transport.submit`` spans that hold them.  One file after another reads a
little under 1; a pool of workers reads the mean number of file passes
running during a transfer."""
from chiplib.program_spans import in_window, seconds


def read(r):
    submits = {s.id: s for s in in_window(r, "transport.submit")}
    passes = [s for s in in_window(r, "transport.copy", "transport.verify")
              if s.parent in submits]
    if not passes:
        return None
    holders = {s.parent for s in passes}
    return seconds(passes) / seconds(submits[i] for i in holders)
