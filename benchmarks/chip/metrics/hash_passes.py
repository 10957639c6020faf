"""Passes of the integrity hash over the saved state: the bytes hashed in the
window by the save's manifest (``ckpt.manifest``), the replication's copies
(``transport.copy``, which hash their source) and its verifications
(``transport.verify``), over the bytes of state pulled from the device
(``ckpt.device_get``)."""
from chiplib.program_spans import in_window, span_bytes


def read(r):
    state = span_bytes(in_window(r, "ckpt.device_get"))
    if not state:
        return None
    return span_bytes(in_window(r, "ckpt.manifest", "transport.copy",
                                "transport.verify")) / state
