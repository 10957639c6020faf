"""Mean host time of ``save_checkpoint`` (device_get, np.save, manifest hash)
per save in the measured window."""


def read(r):
    n, seconds = r.spans.total("ckpt_save", *r.window)
    return seconds / n if n else None
