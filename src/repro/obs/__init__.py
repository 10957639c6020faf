"""Observability: the campaign flight recorder.

The paper's replication succeeded because operators could *see* what 29 M
files were doing — Globus event logs plus the progress database let them
diagnose DTN outages, a 2.5-day network failure, and checksum faults
mid-campaign.  This package gives the simulator the same layer:

  * ``TraceRecorder`` (``repro.obs.trace``) — per-transfer lifecycle spans
    off the ``TransferTable`` row-transition listener, ring-buffered with a
    byte budget, exportable to NDJSON and Chrome trace-event JSON
    (Perfetto-viewable, sim-clock timestamps);
  * ``MetricsRegistry`` (``repro.obs.metrics``) — counters / gauges /
    histograms sampled on a sim-clock cadence: per-route throughput and
    occupancy, queue/backoff depths, fault rates, scrub data-at-risk,
    demand hit-rate;
  * ``Observability`` (``repro.obs.engine``) — the runtime wiring both onto
    a campaign, driven by ``run_world``;
  * ``spans`` (``repro.obs.spans``) — always-on program spans at the
    real-bytes boundaries (checkpoint save/restore, local transport, the
    training loop), with per-name self time, also written into a running
    ``jax.profiler`` trace;
  * ``PhaseProfiler`` (``repro.obs.profile``) — per-phase wall-time buckets
    over the scheduler/transport/table seams, as spans of its own recorder;
  * ``python -m repro.obs.report`` — the post-mortem CLI: days-vs-bytes
    curve, fault/outage timeline, slowest routes, most-retried datasets.

Declared via ``ObsSpec`` on a ``ScenarioSpec``; the default ``NO_OBS``
compiles to **zero hooks**, and the hard contract is bit-identical
trajectories and snapshots with obs on or off.
"""
from repro.obs.spec import FULL_OBS, NO_OBS, ObsSpec

__all__ = ["ObsSpec", "NO_OBS", "FULL_OBS"]
