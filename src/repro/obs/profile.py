"""Per-phase wall-time profiling of the replay hot path.

``PhaseProfiler`` splits a run's wall clock into exclusive per-phase
buckets by temporarily wrapping class methods (scheduler step, transport
tick, table churn, ...).  Promoted out of ``benchmarks/campaign_replay.py``
so the scenario CLI's ``--profile`` and the bench's ``--profile`` share one
implementation; use it as a context manager:

    with PhaseProfiler() as prof:
        prof.instrument_standard()
        run_scenario(...)
    print(prof.report(wall_s))

Instrumentation only *times* the original calls — trajectories are
untouched — but the measured run is slower than a bare one, so profile
numbers belong alongside, never instead of, benchmark walls.
"""
from __future__ import annotations

from typing import List, Tuple

from repro.obs.spans import Recorder


class PhaseProfiler:
    """Per-phase wall-time buckets via temporary class-method wrappers.

    Each wrapped call is a span named by its bucket in a ``Recorder`` of the
    profiler's own, and a bucket's time is its spans' self time: time spent
    in a nested instrumented call (``TransferTable`` work inside
    ``ReplicationScheduler.step``, say) is charged to the inner bucket and
    subtracted from the outer one, so the buckets sum to at most the run's
    wall clock and never double-count.  Wrapping happens at class level so
    federation members (N schedulers over one transport) are all captured.
    """

    def __init__(self):
        self.recorder = Recorder()
        self._patched: List[Tuple[type, str, object]] = []

    def wrap(self, cls, name: str, bucket: str) -> None:
        orig = getattr(cls, name)
        span = self.recorder.span

        def timed(s, *a, _orig=orig, _b=bucket, **kw):
            with span(_b):
                return _orig(s, *a, **kw)

        setattr(cls, name, timed)
        self._patched.append((cls, name, orig))

    def instrument_standard(self) -> "PhaseProfiler":
        """Wrap the canonical hot-path seams: sched (dispatch/poll),
        transport (tick + next-event hints), table (row/index churn),
        and the opt-in control/demand/scrub planes."""
        from repro.control.plane import ControlPlane
        from repro.core.scheduler import ReplicationScheduler
        from repro.core.scrub import ScrubEngine
        from repro.core.transfer_table import TransferTable
        from repro.core.transport import SimulatedTransport
        from repro.demand.engine import DemandEngine

        self.wrap(ReplicationScheduler, "step", "sched")
        self.wrap(SimulatedTransport, "tick", "transport")
        self.wrap(SimulatedTransport, "next_event_hint", "transport")
        self.wrap(TransferTable, "update_many", "table")
        self.wrap(TransferTable, "by_status", "table")
        self.wrap(ControlPlane, "step", "control")
        self.wrap(DemandEngine, "step", "demand")
        self.wrap(ScrubEngine, "step", "scrub")
        return self

    def restore(self) -> None:
        for cls, name, orig in self._patched:
            setattr(cls, name, orig)
        self._patched.clear()

    def __enter__(self) -> "PhaseProfiler":
        return self

    def __exit__(self, *exc) -> None:
        self.restore()

    def report(self, wall_s: float) -> dict:
        """Bucket seconds and percentages, with the unattributed remainder
        of ``wall_s`` charged to a ``driver`` bucket."""
        buckets = {b: t.self_seconds for b, t in self.recorder.totals().items()}
        phases = {b: round(t, 3) for b, t in sorted(buckets.items())}
        phases["driver"] = round(max(0.0, wall_s - sum(buckets.values())), 3)
        return {
            "wall_s": round(wall_s, 3),
            "phases_s": phases,
            "phases_pct": {b: round(100.0 * t / max(wall_s, 1e-9), 1)
                           for b, t in phases.items()},
        }
