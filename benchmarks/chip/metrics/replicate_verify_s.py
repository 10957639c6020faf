"""Host time of the replication's re-read and hash of each copy at its
replica site (program span ``transport.verify``), summed over files and
replica sites, per save in the window."""
from chiplib.program_spans import per_save


def read(r):
    return per_save(r, "transport.verify")
