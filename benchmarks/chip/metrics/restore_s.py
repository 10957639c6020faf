"""Host time of ``restore_anywhere`` after the primary was lost: manifest
verification, the reads and the state's placement on the device."""


def read(r):
    n, seconds = r.spans.total("restore")
    return seconds / n if n else None
