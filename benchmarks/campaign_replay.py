"""Paper §4 / Figure 5 replay: the full 7.3 PB campaign under simulation.

Validates against the paper's own numbers:
  * duration ≈ 77 days (theoretical single-path floor 58 days at 1.5 GB/s);
  * both LCFs end with a complete copy;
  * relay routing carries most OLCF traffic (LLNL read once per dataset);
  * per-route average rates in the neighborhood of Table 3;
  * fault skew: most transfers fault-free, a few with many (Figure 6).

Full scale is 2291 datasets; ``--scale`` trades fidelity for runtime (the
duration figure is scale-invariant because bandwidths and totals shrink
together only when --scale-bytes is also given — by default only file
counts shrink).

``--compare-engines`` additionally replays the paper-2022 scenario under the
fixed-step driver AND the event-driven core (``repro.scenarios.events``) and
records the wall-clock speedup into ``BENCH_scenarios.json``.

``--scaling`` sweeps the catalog size (default n ∈ {48, 512, 2291, 8192,
20480} synthetic datasets) under the event engine and records
wall-clock / iterations / events-per-second per point into
``BENCH_scenarios.json`` — the O(active) acceptance evidence: events/s (and
µs per iteration) must stay flat as the catalog grows.  ``--scenario
mega-campaign`` replays the ≥20k-dataset four-site registry scenario.

``--checkpoint-bench`` measures the durable-checkpoint tax: a cadenced
snapshot run vs a bare run, with the (required) bit-identical-trajectory
verdict, mean write latency, and snapshot size recorded under the
``checkpointing`` key of ``BENCH_scenarios.json``.

``--federation-bench`` replays the overlapped two-campaign federation
(``federation-paper-twice``) under both engines, checks the shared
source-egress cap at every tick, compares the span against the serial
back-to-back variant, and records everything (per-member digests included)
under the ``federation`` key of ``BENCH_scenarios.json``.

``--demand-bench`` replays ``esgf-serving`` popular-first (both engines),
the catalog-order ablation, and the no-traffic comparator, and records the
serving SLOs plus the popular-first-beats-catalog-order verdict under the
``demand`` key of ``BENCH_scenarios.json``.

``--ensemble-bench`` gates the batched ensemble engine's worlds/sec
scaling: a 256-lane lockstep pass of ``ensemble-paper-bands`` must beat
256 sequential scalar replays by >=20x, with every sampled lane matching
its scalar trajectory bit-for-bit, recorded under the ``ensemble`` key of
``BENCH_scenarios.json``.

``--integrity-bench`` replays ``scrub-and-repair`` (both engines), the
``bit-rot-paper`` no-scrub ablation, and the corruption-free comparator,
and records the integrity summaries plus the ends-clean / repairs-converge
/ exposure / repair-tax verdicts under the ``integrity`` key of
``BENCH_scenarios.json``.

``--obs-bench`` gates the flight recorder: a paper-2022 replay with trace +
metrics on must stay within 1.10x the obs-off wall (same-process
min-of-repeats) with a bit-identical trajectory tuple, recorded under the
``obs`` key of ``BENCH_scenarios.json``.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core.campaign import CampaignConfig, run_campaign
from repro.obs.profile import PhaseProfiler

SCALING_NS = (48, 512, 2291, 8192, 20480)


def replay(n_datasets: int = 2291, scale: float = 1.0, seed: int = 0,
           step_s: float = 1800.0):
    cfg = CampaignConfig(n_datasets=n_datasets, scale=scale, seed=seed,
                         step_s=step_s)
    t0 = time.time()
    rep = run_campaign(cfg)
    wall = time.time() - t0
    out = {
        "wall_s": wall,
        "duration_days": rep.duration_days,
        "floor_days": rep.floor_days,
        "paper_duration_days": 77.0,
        "paper_floor_days": 58.0,
        "complete_at_both": all(v >= rep.total_bytes * 0.999
                                for v in rep.bytes_at.values()),
        "per_route_gbps": {f"{a}->{b}": round(v, 3)
                           for (a, b), v in rep.per_route_gbps.items()},
        "per_route_transfers": {f"{a}->{b}": v
                                for (a, b), v in rep.per_route_transfers.items()},
        "paper_table3_gbps": {"LLNL->ALCF": 0.648, "LLNL->OLCF": 0.662,
                              "ALCF->OLCF": 1.706, "OLCF->ALCF": 2.352},
        "faults_total": rep.faults_total,
        "paper_faults_total": 4086,
        "faults_mean": round(rep.faults_per_transfer_mean, 2),
        "faults_max": rep.faults_per_transfer_max,
        "quarantined": rep.quarantined,
        "notifications": len(rep.notifications),
    }
    return out, rep


def compare_engines(n_datasets: int = 48, scale: float = 1.0, seed: int = 0):
    """Step-driven vs event-driven replay of the paper-2022 scenario: same
    catalog, calendar, and fault seeds; records wall clock, driver
    iterations, and the behavior deltas that must stay small."""
    from repro.scenarios.events import EngineStats, run_scenario

    results = {}
    for engine in ("step", "events"):
        stats = EngineStats()
        t0 = time.time()
        rep = run_scenario("paper-2022", engine=engine, scale=scale,
                           seed=seed, n_datasets=n_datasets, stats=stats)
        results[engine] = {
            "wall_s": round(time.time() - t0, 3),
            "iterations": stats.iterations,
            "duration_days": round(rep.duration_days, 3),
            "faults_total": rep.faults_total,
            "faults_max": rep.faults_per_transfer_max,
            "quarantined": rep.quarantined,
        }
    step, ev = results["step"], results["events"]
    return {
        "n_datasets": n_datasets,
        "scale": scale,
        "seed": seed,
        "step": step,
        "events": ev,
        "speedup": round(step["wall_s"] / max(ev["wall_s"], 1e-9), 2),
        "duration_delta_pct": round(
            100.0 * abs(ev["duration_days"] - step["duration_days"])
            / max(step["duration_days"], 1e-9), 3),
    }


def scaling_point(n_datasets: int, scenario: str = "paper-2022",
                  seed: int = 0, scale: float = 1.0) -> dict:
    """One event-engine replay at catalog size ``n_datasets``, reduced to
    the scaling metrics: wall clock, driver iterations, events/s, and the
    per-iteration cost that must stay flat in catalog size."""
    from repro.scenarios.events import EngineStats, run_scenario
    stats = EngineStats()
    t0 = time.time()
    rep = run_scenario(scenario, engine="events", scale=scale, seed=seed,
                       n_datasets=n_datasets, stats=stats)
    wall = time.time() - t0
    return {
        "n_datasets": n_datasets,
        "wall_s": round(wall, 3),
        "iterations": stats.iterations,
        "events_per_s": round(stats.iterations / max(wall, 1e-9), 1),
        "us_per_iteration": round(1e6 * wall / max(stats.iterations, 1), 1),
        "duration_days": round(rep.duration_days, 3),
        "faults_total": rep.faults_total,
        "quarantined": rep.quarantined,
    }


def checkpoint_bench(n_datasets: int = 48, every: int = 25, seed: int = 0,
                     workdir: str = None) -> dict:
    """Cost of durable checkpointing on the paper-2022 event replay: run
    uninterrupted, then again with a snapshot every ``every`` iterations,
    and report write cadence cost, snapshot size, and — the load-bearing
    bit — that the checkpointed trajectory is identical to the bare one."""
    import shutil
    import tempfile

    from repro.core.snapshot import Checkpointer, trajectory_summary
    from repro.scenarios.events import EngineStats, run_world
    from repro.scenarios.registry import get_scenario

    spec = get_scenario("paper-2022")
    world = spec.build(seed=seed, n_datasets=n_datasets)
    stats = EngineStats()
    t0 = time.time()
    rep = run_world(world, stats=stats)
    bare_wall = time.time() - t0
    ref = trajectory_summary(rep, stats, world.table)

    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="ckpt-bench-")
    world2 = spec.build(seed=seed, n_datasets=n_datasets)
    stats2 = EngineStats()
    ck = Checkpointer(workdir, every=every)
    t0 = time.time()
    rep2 = run_world(world2, stats=stats2, checkpointer=ck)
    wall = time.time() - t0
    res = trajectory_summary(rep2, stats2, world2.table)
    if own_dir:
        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "n_datasets": n_datasets,
        "every": every,
        "iterations": stats2.iterations,
        "writes": ck.writes,
        "write_ms_mean": round(1000.0 * ck.write_s / max(1, ck.writes), 2),
        "snapshot_bytes": ck.last_bytes,
        "bare_wall_s": round(bare_wall, 3),
        "wall_s": round(wall, 3),
        "overhead_pct": round(100.0 * (wall - bare_wall) / max(bare_wall, 1e-9),
                              1),
        "identical_to_bare": res == ref,
        "succeeded_digest": ref["succeeded_digest"],
    }


def federation_bench(n_datasets: int = 32, seed: int = 0,
                     repeats: int = 3) -> dict:
    """The federation acceptance experiment, benchmarked: replay the
    overlapped two-campaign federation under BOTH engines (determinism and
    wall clock recorded like ``engine_comparison``), the serial back-to-back
    variant, and the relay-assisted single-campaign comparator.  Records:

      * per-engine iterations / span / faults / per-member digests — the
        determinism invariants the regression gate pins;
      * ``source_cap_ok`` — at every transport tick of the overlapped run,
        aggregate LLNL egress (sum of per-route fair shares × actives) never
        exceeded the LLNL ``read_bw``;
      * ``overlap_beats_serial`` — the overlapped federation's span in
        campaign days beats the serial variant's.
    """
    from repro.core.snapshot import federation_trajectory_summary
    from repro.scenarios.events import EngineStats, run_world
    from repro.scenarios.registry import get_scenario

    results = {}
    for engine in ("step", "events"):
        # wall clock is min-of-``repeats`` (sub-second replays are noisy on
        # shared CI runners); trajectories are identical across repeats
        walls = []
        for _ in range(max(1, repeats)):
            world = get_scenario("federation-paper-twice").build(
                seed=seed, n_datasets=n_datasets)
            transport = world.shared.transport
            read_bw = world.shared.graph.sites["LLNL"].read_bw
            cap = {"ok": True, "max_frac": 0.0}
            orig = transport._route_rates

            def route_rates(movers, _orig=orig, _cap=cap):
                rates = _orig(movers)
                active = {}
                for x in movers:
                    r = (x.source, x.destination)
                    active[r] = active.get(r, 0) + 1
                egress = sum(rates[r] * n for r, n in active.items()
                             if r[0] == "LLNL")
                _cap["max_frac"] = max(_cap["max_frac"], egress / read_bw)
                if egress > read_bw * (1 + 1e-9):
                    _cap["ok"] = False
                return rates

            transport._route_rates = route_rates
            stats = EngineStats()
            t0 = time.time()
            rep = run_world(world, engine=engine, stats=stats)
            walls.append(time.time() - t0)
        summ = federation_trajectory_summary(rep, stats, world)
        results[engine] = {
            "wall_s": round(min(walls), 3),
            "iterations": stats.iterations,
            "span_days": round(rep.span_days, 3),
            "faults_total": sum(m.faults_total for m in rep.members.values()),
            "source_cap_ok": cap["ok"],
            "source_cap_max_frac": round(cap["max_frac"], 4),
            "members": {label: {
                "sim_days": round(m["sim_days"], 3),
                "succeeded_digest": m["succeeded_digest"],
            } for label, m in summ["members"].items()},
        }

    serial_world = get_scenario("federation-paper-serial").build(
        seed=seed, n_datasets=n_datasets)
    serial_stats = EngineStats()
    serial_rep = run_world(serial_world, engine="events", stats=serial_stats)

    relay_stats = EngineStats()
    relay_world = get_scenario("paper-2022").build(seed=seed,
                                                   n_datasets=n_datasets)
    relay_rep = run_world(relay_world, engine="events", stats=relay_stats)

    step, ev = results["step"], results["events"]
    return {
        "scenario": "federation-paper-twice",
        "n_datasets": n_datasets,
        "seed": seed,
        "step": step,
        "events": ev,
        "speedup": round(step["wall_s"] / max(ev["wall_s"], 1e-9), 2),
        "serial_span_days": round(serial_rep.span_days, 3),
        "relay_single_days": round(relay_rep.duration_days, 3),
        "overlap_beats_serial": ev["span_days"] < serial_rep.span_days,
    }


# demand-bench shape: small enough for CI, enough catalog + traffic that the
# popular-first ordering measurably moves the serving SLOs
DEMAND_SHAPE = dict(n_datasets=32, scale=0.02)


def demand_bench(seed: int = 0) -> dict:
    """The demand-engine acceptance experiment: replay esgf-serving
    popular-first (both engines), the catalog-order ablation, and the
    no-traffic comparator, recording each arm's determinism tuple
    (iterations, float-exact sim days, faults, succeeded digest) plus the
    serving SLOs.  Carries the headline verdicts:

      * ``popular_first_beats_catalog_order`` — popularity-driven
        replication reaches a better overall hit-rate and an
        as-early-or-earlier time-to-90%-hit-rate day than catalog-order
        replication under identical traffic;
      * ``traffic_tax_ok`` — serving 2M users while replicating costs at
        most 50% extra campaign days over the no-traffic baseline.
    """
    from repro.core.snapshot import trajectory_summary
    from repro.demand.spec import NO_DEMAND
    from repro.scenarios.events import EngineStats, run_world
    from repro.scenarios.registry import get_scenario

    arms = {
        "popular_first": (get_scenario("esgf-serving"), ("events", "step")),
        "catalog_order": (get_scenario("popular-first-vs-catalog-order"),
                          ("events",)),
        "no_traffic": (get_scenario("esgf-serving").with_demand(NO_DEMAND),
                       ("events",)),
    }
    out = {"seed": seed, "shape": dict(DEMAND_SHAPE), "arms": {}}
    for label, (spec, engines) in arms.items():
        for engine in engines:
            world = spec.build(seed=seed, **DEMAND_SHAPE)
            stats = EngineStats()
            t0 = time.time()
            rep = run_world(world, engine=engine, stats=stats)
            wall = time.time() - t0
            traj = trajectory_summary(rep, stats, world.table)
            key = label if engine == "events" else f"{label}_{engine}"
            arm = {
                "wall_s": round(wall, 3),
                "iterations": stats.iterations,
                "sim_days": rep.duration_days,
                "faults_total": rep.faults_total,
                "quarantined": rep.quarantined,
                "succeeded_digest": traj["succeeded_digest"],
            }
            if world.demand is not None:
                s = world.demand.summary()
                arm["serving"] = {
                    k: s[k] for k in
                    ("waves", "requests", "hit_rate", "cache_hit_rate",
                     "source_reads", "p50_s", "p99_s", "day90",
                     "final_day_hit_rate")}
            out["arms"][key] = arm
            print(f"{key:20} {arm['sim_days']:8.3f} d "
                  f"({arm['wall_s']:.2f}s)"
                  + (f"  hit={arm['serving']['hit_rate']*100:.1f}% "
                     f"day90={arm['serving']['day90']} "
                     f"p99={arm['serving']['p99_s']}s"
                     if "serving" in arm else ""))
    pf = out["arms"]["popular_first"]["serving"]
    co = out["arms"]["catalog_order"]["serving"]
    inf = float("inf")
    out["popular_first_beats_catalog_order"] = (
        pf["hit_rate"] > co["hit_rate"]
        and (inf if pf["day90"] is None else pf["day90"])
        <= (inf if co["day90"] is None else co["day90"]))
    out["traffic_tax_ok"] = (
        out["arms"]["popular_first"]["sim_days"]
        <= out["arms"]["no_traffic"]["sim_days"] * 1.5)
    return out


# integrity-bench shape: small enough for CI, enough landed petabytes that
# the accelerated latent-corruption rate draws a handful of corrupt replicas
INTEGRITY_SHAPE = dict(n_datasets=32, scale=0.02)


def integrity_bench(seed: int = 0) -> dict:
    """The silent-corruption acceptance experiment: replay scrub-and-repair
    (both engines), the no-scrub bit-rot ablation, and the corruption-free
    comparator, recording each arm's determinism tuple plus the integrity
    summary (detections, repairs, exposure replica-days, surviving at-risk
    bytes).  Carries the headline verdicts:

      * ``ends_clean`` — every scrub arm finishes with zero corrupt
        replicas (detected > 0, repaired == detected, clean);
      * ``repairs_converge`` — the scrub arm's final SUCCEEDED replica set
        is identical (set digest) to the corruption-free run's end state;
      * ``ablation_survives_corrupt`` — with scrubbing disabled the same
        draws leave silently corrupt replicas at campaign end;
      * ``exposure_ok`` — total at-risk exposure stays under 3 scrub
        intervals per detected replica;
      * ``repair_tax_ok`` — scrubbing + repairs cost at most 75% extra
        campaign days over the corruption-free baseline.
    """
    from repro.core.scrub import NO_SCRUB
    from repro.core.snapshot import replica_set_digest, trajectory_summary
    from repro.scenarios.events import EngineStats, run_world
    from repro.scenarios.registry import get_scenario

    arms = {
        "scrub_repair": (get_scenario("scrub-and-repair"),
                         ("events", "step")),
        "no_scrub": (get_scenario("bit-rot-paper"), ("events",)),
        "clean": (get_scenario("scrub-and-repair").with_scrub(NO_SCRUB),
                  ("events",)),
    }
    out = {"seed": seed, "shape": dict(INTEGRITY_SHAPE), "arms": {}}
    for label, (spec, engines) in arms.items():
        for engine in engines:
            world = spec.build(seed=seed, **INTEGRITY_SHAPE)
            stats = EngineStats()
            t0 = time.time()
            rep = run_world(world, engine=engine, stats=stats)
            wall = time.time() - t0
            traj = trajectory_summary(rep, stats, world.table)
            key = label if engine == "events" else f"{label}_{engine}"
            arm = {
                "wall_s": round(wall, 3),
                "iterations": stats.iterations,
                "sim_days": rep.duration_days,
                "faults_total": rep.faults_total,
                "quarantined": rep.quarantined,
                "succeeded_digest": traj["succeeded_digest"],
                "replica_digest": replica_set_digest(world.table),
            }
            if world.scrub is not None:
                arm["integrity"] = world.scrub.summary()
            out["arms"][key] = arm
            print(f"{key:20} {arm['sim_days']:8.3f} d "
                  f"({arm['wall_s']:.2f}s)"
                  + (f"  detected={arm['integrity']['detected']} "
                     f"repaired={arm['integrity']['repaired']} "
                     f"exposure={arm['integrity']['exposure_days']}d "
                     f"{'CLEAN' if arm['integrity']['clean'] else 'AT RISK'}"
                     if "integrity" in arm else ""))
    sr = out["arms"]["scrub_repair"]
    interval = get_scenario("scrub-and-repair").scrub.interval_days
    out["ends_clean"] = all(
        a["integrity"]["clean"] and a["integrity"]["detected"] > 0
        and a["integrity"]["repaired"] == a["integrity"]["detected"]
        for a in (sr, out["arms"]["scrub_repair_step"]))
    out["repairs_converge"] = (
        sr["replica_digest"] == out["arms"]["clean"]["replica_digest"])
    ab = out["arms"]["no_scrub"]["integrity"]
    out["ablation_survives_corrupt"] = (
        not ab["clean"] and ab["data_at_risk_bytes"] > 0)
    out["exposure_ok"] = (
        sr["integrity"]["exposure_days"]
        <= 3.0 * interval * max(1, sr["integrity"]["detected"]))
    out["repair_tax_ok"] = (
        sr["sim_days"] <= out["arms"]["clean"]["sim_days"] * 1.75)
    return out


# policy-bench shapes: small enough for CI, large enough that the task-
# dispatch overhead the control plane amortizes actually dominates static
POLICY_SHAPES = {
    "small-file-storm": dict(n_datasets=200, scale=0.2),
    "mixed-bundle-paper": dict(n_datasets=24, scale=0.01),
    # enough bytes (0.73 PB) that the kneed source bandwidth — not the
    # maintenance calendar — bounds the campaign
    "lossy-route-tuning": dict(n_datasets=32, scale=0.1),
}


def policy_bench(seed: int = 0) -> dict:
    """The control-plane acceptance experiment: replay each policy scenario
    under its declared adaptive policy AND under the naive static
    per-dataset baseline, and record the determinism tuple (iterations,
    float-exact sim days, faults, succeeded digest) plus wall clock for
    each.  ``small-file-storm`` additionally runs both driver engines per
    policy, and carries the headline verdict: adaptive bundling must finish
    in no more simulated campaign days than the static baseline — the
    simulator's quantitative version of 'Globus-style bundling beats
    scripted per-dataset submission on small-file-heavy catalogs'."""
    from repro.control.policy import STATIC_POLICY
    from repro.core.snapshot import trajectory_summary
    from repro.scenarios.events import EngineStats, run_world
    from repro.scenarios.registry import get_scenario

    out = {"seed": seed,
           "shapes": {k: dict(v) for k, v in POLICY_SHAPES.items()},
           "scenarios": {}}
    for name, shape in POLICY_SHAPES.items():
        block = {}
        engines = (("events", "step") if name == "small-file-storm"
                   else ("events",))
        for label in ("static", "adaptive"):
            spec = get_scenario(name)
            if label == "static":
                spec = spec.with_policy(STATIC_POLICY)
            for engine in engines:
                world = spec.build(seed=seed, **shape)
                stats = EngineStats()
                t0 = time.time()
                rep = run_world(world, engine=engine, stats=stats)
                wall = time.time() - t0
                traj = trajectory_summary(rep, stats, world.table)
                key = label if engine == "events" else f"{label}_{engine}"
                block[key] = {
                    "wall_s": round(wall, 3),
                    "iterations": stats.iterations,
                    "sim_days": rep.duration_days,
                    "faults_total": rep.faults_total,
                    "quarantined": rep.quarantined,
                    "succeeded_digest": traj["succeeded_digest"],
                }
        block["adaptive_beats_static"] = (
            block["adaptive"]["sim_days"] <= block["static"]["sim_days"])
        out["scenarios"][name] = block
        print(f"{name:20} static {block['static']['sim_days']:8.3f} d "
              f"({block['static']['wall_s']:.2f}s) vs adaptive "
              f"{block['adaptive']['sim_days']:8.3f} d "
              f"({block['adaptive']['wall_s']:.2f}s)"
              + ("  ADAPTIVE WINS" if block["adaptive_beats_static"]
                 else "  !! static wins"))
    return out


def ensemble_bench(n_lanes: int = 256, scale: float = 0.002,
                   n_datasets: int = 4, sample: int = 8,
                   min_speedup: float = 20.0) -> dict:
    """Worlds/sec scaling gate for the batched ensemble engine: one
    N-lane lockstep pass of ``ensemble-paper-bands`` must beat N
    sequential scalar replays of the identical lanes by ``min_speedup``x.

    Protocol: ``sample`` lanes replay through the scalar event engine
    first (sequentially, the way a seed sweep runs without the lanes
    engine) and project to N; the lanes engine then runs the full
    ensemble twice (best-of-2 — the first pass pays allocator warm-up).
    Speedup is a same-process, same-machine ratio, so runner speed
    cancels.  Every sampled lane must match its lanes-engine row on the
    full trajectory tuple — the bit-identity contract, enforced here on
    ``sample`` lanes, not just lane 0."""
    import dataclasses

    from repro.ensemble.engine import run_ensemble, scalar_lane
    from repro.ensemble.run import GATE_FIELDS
    from repro.scenarios.registry import get_scenario

    espec = dataclasses.replace(get_scenario("ensemble-paper-bands"),
                                n_lanes=n_lanes)
    lanes = espec.lane_specs()
    t0 = time.time()
    refs = [scalar_lane(spec, seed, label, scale, n_datasets)
            for spec, seed, label in lanes[:sample]]
    scalar_sample_s = time.time() - t0
    scalar_projected_s = scalar_sample_s / sample * n_lanes

    walls = []
    for _ in range(2):
        t0 = time.time()
        res = run_ensemble(espec, scale=scale, n_datasets=n_datasets)
        walls.append(time.time() - t0)
    lanes_wall_s = min(walls)

    mismatches = {}
    for i, ref in enumerate(refs):
        got = res.lane(i)
        diff = {f: {"scalar": getattr(ref, f), "lanes": getattr(got, f)}
                for f in GATE_FIELDS if getattr(ref, f) != getattr(got, f)}
        if diff:
            mismatches[i] = diff
    speedup = scalar_projected_s / max(lanes_wall_s, 1e-9)
    doc = {
        "ensemble": espec.name, "n_lanes": n_lanes, "scale": scale,
        "n_datasets": n_datasets, "engine": res.engine,
        "backend": res.backend, "sample": sample,
        "scalar_sample_s": round(scalar_sample_s, 3),
        "scalar_projected_s": round(scalar_projected_s, 3),
        "lanes_wall_s": round(lanes_wall_s, 3),
        "speedup": round(speedup, 1),
        "min_speedup": min_speedup,
        "lanes_identical": not mismatches,
        "mismatches": mismatches,
        "lane0": {f: getattr(res.lane(0), f)
                  for f in GATE_FIELDS if f != "bytes_at"},
        "bands": res.bands,
        "gate_ok": (not mismatches) and speedup >= min_speedup,
    }
    print(f"ensemble {espec.name}: {n_lanes} lanes in {lanes_wall_s:.3f}s "
          f"vs {scalar_projected_s:.2f}s projected sequential "
          f"({scalar_sample_s:.2f}s for {sample}) -> {speedup:.1f}x "
          + ("OK" if doc["gate_ok"]
             else f"!! gate FAILED (need >={min_speedup}x, "
                  f"identical={not mismatches})"))
    return doc


def profile_run(scenario: str = "paper-2022", n_datasets: int = None,
                seed: int = 0, scale: float = 1.0) -> dict:
    """One instrumented event-engine replay split into per-phase buckets:
    sched (dispatch/poll), transport (tick + next-event hints), table
    (row/index churn, charged exclusively), control/demand/scrub (the
    opt-in planes), and driver (the run_world loop remainder).  Thin
    wrapper over ``repro.obs.profile.PhaseProfiler``."""
    from repro.scenarios.events import EngineStats, run_scenario

    stats = EngineStats()
    t0 = time.time()
    with PhaseProfiler() as prof:
        prof.instrument_standard()
        run_scenario(scenario, engine="events", scale=scale, seed=seed,
                     n_datasets=n_datasets, stats=stats)
    wall = time.time() - t0
    doc = prof.report(wall)
    return {
        "scenario": scenario,
        "n_datasets": n_datasets,
        "seed": seed,
        "wall_s": doc["wall_s"],
        "iterations": stats.iterations,
        "phases_s": doc["phases_s"],
        "phases_pct": doc["phases_pct"],
    }


def obs_bench(n_datasets: int = 2291, seed: int = 0, scale: float = 1.0,
              repeats: int = 3, max_overhead: float = 1.10) -> dict:
    """The flight-recorder overhead + determinism gate: paper-2022 replayed
    obs-off and obs-on (trace + metrics, in-memory — no sink I/O in the
    measured loop), min-of-repeats walls, and the trajectory tuples that
    must match bit-exactly.  Both arms run in this same process, so the
    ratio cancels machine speed and the gate travels."""
    from repro.core.snapshot import trajectory_summary
    from repro.obs.spec import FULL_OBS
    from repro.scenarios.events import EngineStats, run_world
    from repro.scenarios.registry import get_scenario

    spec_off = get_scenario("paper-2022")
    spec_on = spec_off.with_obs(FULL_OBS)
    arms = {}
    # interleave the arms so clock drift (thermal, background load) hits
    # both equally instead of biasing whichever ran second
    for _ in range(repeats):
        for arm, spec in (("obs_off", spec_off), ("obs_on", spec_on)):
            world = spec.build(scale=scale, seed=seed, n_datasets=n_datasets)
            stats = EngineStats()
            t0 = time.perf_counter()
            rep = run_world(world, engine="events", stats=stats)
            wall = time.perf_counter() - t0
            traj = trajectory_summary(rep, stats, world.table)
            best = arms.get(arm)
            if best is None or wall < best["wall_s"]:
                arms[arm] = {"wall_s": wall, "trajectory": traj}
    for best in arms.values():
        best["wall_s"] = round(best["wall_s"], 3)
    ratio = arms["obs_on"]["wall_s"] / max(arms["obs_off"]["wall_s"], 1e-9)
    identical = arms["obs_on"]["trajectory"] == arms["obs_off"]["trajectory"]
    doc = {
        "scenario": "paper-2022",
        "n_datasets": n_datasets,
        "seed": seed,
        "scale": scale,
        "repeats": repeats,
        "obs_off": arms["obs_off"],
        "obs_on": arms["obs_on"],
        "overhead_ratio": round(ratio, 3),
        "max_overhead": max_overhead,
        "obs_identical": identical,
        "gate_ok": identical and ratio <= max_overhead,
    }
    print(f"obs paper-2022 n={n_datasets}: off={arms['obs_off']['wall_s']:.3f}s "
          f"on={arms['obs_on']['wall_s']:.3f}s -> {ratio:.3f}x "
          + ("OK" if doc["gate_ok"]
             else f"!! gate FAILED (need <={max_overhead}x, "
                  f"identical={identical})"))
    return doc


def scaling(ns=SCALING_NS, scenario: str = "paper-2022", seed: int = 0) -> dict:
    rows = []
    for n in ns:
        row = scaling_point(n, scenario=scenario, seed=seed)
        rows.append(row)
        print(f"n={n:6d}  wall={row['wall_s']:8.2f}s  "
              f"iters={row['iterations']:7d}  "
              f"{row['events_per_s']:8.1f} ev/s  "
              f"{row['us_per_iteration']:7.1f} us/iter  "
              f"{row['duration_days']:7.2f} d")
    return {"scenario": scenario, "seed": seed, "points": rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", type=int, default=2291)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--scenario", default="paper-2022")
    ap.add_argument("--out", default=None)
    ap.add_argument("--compare-engines", action="store_true",
                    help="benchmark step vs event engine on paper-2022 and "
                         "record the speedup in BENCH_scenarios.json")
    ap.add_argument("--checkpoint-bench", action="store_true",
                    help="measure durable-checkpoint overhead (cadenced "
                         "snapshots vs bare run) and record it in "
                         "BENCH_scenarios.json")
    ap.add_argument("--checkpoint-every", type=int, default=25,
                    help="snapshot cadence for --checkpoint-bench")
    ap.add_argument("--policy-bench", action="store_true",
                    help="compare the control plane's adaptive policies "
                         "against the static per-dataset baseline on the "
                         "policy scenarios and record it in "
                         "BENCH_scenarios.json")
    ap.add_argument("--demand-bench", action="store_true",
                    help="compare popular-first vs catalog-order vs "
                         "no-traffic serving on esgf-serving and record it "
                         "in BENCH_scenarios.json")
    ap.add_argument("--integrity-bench", action="store_true",
                    help="compare scrub-and-repair vs the no-scrub bit-rot "
                         "ablation vs the corruption-free baseline and "
                         "record it in BENCH_scenarios.json")
    ap.add_argument("--federation-bench", action="store_true",
                    help="benchmark the overlapped two-campaign federation "
                         "vs its serial variant (both engines, source-cap "
                         "check) and record it in BENCH_scenarios.json")
    ap.add_argument("--ensemble-bench", action="store_true",
                    help="gate the batched ensemble engine's worlds/sec "
                         "scaling (N=256 lockstep vs N sequential scalar "
                         "replays, bit-identity enforced on sampled lanes) "
                         "and record it in BENCH_scenarios.json")
    ap.add_argument("--obs-bench", action="store_true",
                    help="gate the flight recorder: obs-on paper-2022 "
                         "replay <= 1.10x obs-off wall with a bit-identical "
                         "trajectory, recorded in BENCH_scenarios.json")
    ap.add_argument("--ensemble-lanes", type=int, default=256,
                    help="lane count for --ensemble-bench")
    ap.add_argument("--min-speedup", type=float, default=20.0,
                    help="speedup floor for --ensemble-bench")
    ap.add_argument("--scaling", action="store_true",
                    help="replay --scenario at increasing catalog sizes and "
                         "record the scaling curve in BENCH_scenarios.json")
    ap.add_argument("--scaling-ns", default=None,
                    help="comma-separated catalog sizes for --scaling "
                         f"(default {','.join(map(str, SCALING_NS))})")
    ap.add_argument("--profile", action="store_true",
                    help="instrumented replay splitting wall time into "
                         "sched/transport/table/control/demand/scrub/driver "
                         "buckets; alone it profiles --scenario at "
                         "--datasets, with --scaling it attaches the "
                         "breakdown at the largest sweep point")
    ap.add_argument("--bench-out", default="BENCH_scenarios.json")
    args = ap.parse_args()
    from repro.scenarios.sweep import emit_bench
    if args.scaling:
        ns = (tuple(int(s) for s in args.scaling_ns.split(","))
              if args.scaling_ns else SCALING_NS)
        doc = scaling(ns, scenario=args.scenario)
        if args.profile:
            doc["profile"] = profile_run(args.scenario, n_datasets=max(ns))
            print(json.dumps(doc["profile"], indent=2))
        key = ("scaling" if args.scenario == "paper-2022"
               else f"scaling_{args.scenario}")
        emit_bench([], path=args.bench_out, extra={key: doc})
        return
    if args.profile:
        datasets = args.datasets if args.datasets != 2291 else None
        doc = profile_run(args.scenario, n_datasets=datasets,
                          scale=args.scale)
        key = ("profile" if args.scenario == "paper-2022"
               else f"profile_{args.scenario}")
        emit_bench([], path=args.bench_out, extra={key: doc})
        print(json.dumps(doc, indent=2))
        return
    if args.obs_bench:
        doc = obs_bench(n_datasets=args.datasets, scale=args.scale)
        emit_bench([], path=args.bench_out, extra={"obs": doc})
        print(json.dumps(doc, indent=2))
        if not doc["gate_ok"]:
            raise SystemExit(1)
        return
    if args.ensemble_bench:
        doc = ensemble_bench(n_lanes=args.ensemble_lanes,
                             min_speedup=args.min_speedup)
        emit_bench([], path=args.bench_out, extra={"ensemble": doc})
        print(json.dumps({k: v for k, v in doc.items() if k != "bands"},
                         indent=2))
        if not doc["gate_ok"]:
            raise SystemExit(1)
        return
    if args.policy_bench:
        doc = policy_bench()
        emit_bench([], path=args.bench_out, extra={"policy": doc})
        print(json.dumps(doc, indent=2))
        return
    if args.demand_bench:
        doc = demand_bench()
        emit_bench([], path=args.bench_out, extra={"demand": doc})
        print(json.dumps(doc, indent=2))
        return
    if args.integrity_bench:
        doc = integrity_bench()
        emit_bench([], path=args.bench_out, extra={"integrity": doc})
        print(json.dumps(doc, indent=2))
        return
    if args.federation_bench:
        doc = federation_bench(n_datasets=min(args.datasets, 32))
        emit_bench([], path=args.bench_out, extra={"federation": doc})
        print(json.dumps(doc, indent=2))
        return
    if args.checkpoint_bench:
        doc = checkpoint_bench(n_datasets=min(args.datasets, 48),
                               every=args.checkpoint_every)
        emit_bench([], path=args.bench_out, extra={"checkpointing": doc})
        print(json.dumps(doc, indent=2))
        return
    if args.compare_engines:
        cmp = compare_engines(n_datasets=min(args.datasets, 48),
                              scale=args.scale)
        emit_bench([], path=args.bench_out,
                   extra={"engine_comparison": cmp})
        print(json.dumps(cmp, indent=2))
        return
    if args.scenario != "paper-2022":
        # non-paper scenarios replay through the event engine
        out = scaling_point(args.datasets, scenario=args.scenario,
                            scale=args.scale)
        print(json.dumps(out, indent=2))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=2)
        return
    out, rep = replay(args.datasets, args.scale)
    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)


if __name__ == "__main__":
    main()
