"""Host time of the save's ``device_get`` of every leaf (program span
``ckpt.device_get``), summed over the leaves, per save in the window."""
from chiplib.program_spans import per_save


def read(r):
    return per_save(r, "ckpt.device_get")
