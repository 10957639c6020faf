"""Host time of the save's manifest (program span ``ckpt.manifest``: every
file re-read and hashed, the manifest written), per save in the window."""
from chiplib.program_spans import per_save


def read(r):
    return per_save(r, "ckpt.manifest")
