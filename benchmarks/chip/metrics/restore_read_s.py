"""Host time of the restore's reads (program span ``ckpt.read``: each leaf's
``np.load`` and concatenation, and the placement of bfloat16 leaves) inside
the benchmark's ``restore`` span."""
from chiplib.program_spans import per_restore


def read(r):
    return per_restore(r, "ckpt.read")
