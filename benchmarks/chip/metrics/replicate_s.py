"""Mean host time of ``CheckpointReplicator.replicate`` (scheduler, transport
copies, verification at both replica sites) per save in the measured window."""


def read(r):
    n, seconds = r.spans.total("replicate", *r.window)
    return seconds / n if n else None
