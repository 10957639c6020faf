"""Host time of the save's file writes (program span ``ckpt.write``: each
leaf's ``np.save`` of its chunks, ``tree.json`` and the data state), summed,
per save in the window."""
from chiplib.program_spans import per_save


def read(r):
    return per_save(r, "ckpt.write")
