"""Host time of the restore's manifest verification (program span
``ckpt.verify``: every file of the replica re-read and hashed) inside the
benchmark's ``restore`` span."""
from chiplib.program_spans import per_restore


def read(r):
    return per_restore(r, "ckpt.verify")
