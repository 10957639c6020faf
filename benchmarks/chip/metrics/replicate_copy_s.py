"""Host time of the replication's file copies, each hashing its source as it
streams (program span ``transport.copy``), summed over files and replica
sites, per save in the window."""
from chiplib.program_spans import per_save


def read(r):
    return per_save(r, "transport.copy")
