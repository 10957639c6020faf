"""The program's own spans (``repro.obs.spans``) as the per-layer readers see
them.  They share the benchmark's clock (``time.perf_counter()`` in the same
process), so the measured window and the benchmark's own spans select them.
On a program that records no such spans every reader gets ``None``; nothing
here raises."""


def records():
    """The spans in the program's process-wide recorder, or ``None`` where
    the program has no recorder."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    return spans.records()


def in_window(r, *names) -> list:
    """The program's spans named in ``names`` that began inside the measured
    window."""
    t0, t1 = r.window
    return [s for s in records() or ()
            if s.name in names and t0 <= s.start <= t1]


def seconds(spans) -> float:
    return sum(s.seconds for s in spans)


def span_bytes(spans) -> int:
    return sum(s.attrs.get("bytes", 0) for s in spans)


def per_save(r, name: str):
    """Seconds of the ``name`` spans in the window, summed, per save."""
    saves = len(in_window(r, "ckpt.save"))
    return seconds(in_window(r, name)) / saves if saves else None


def per_restore(r, name: str):
    """Seconds of the ``name`` spans nested in the benchmark's own
    ``restore`` spans, summed, per restore.  The untimed comparison restore
    of the other replica lies outside those spans and is left out."""
    outer = [(a, b) for n, a, b in r.spans.records if n == "restore"]
    inner = [s for s in records() or () if s.name == name
             and any(a <= s.start and s.end <= b for a, b in outer)]
    return seconds(inner) / len(outer) if inner else None
