"""Ensemble engine: lane-0 (and every-lane) bit-identity vs the scalar
event engine, backend elementwise agreement, scalar fallback, the search
driver's checkpoint/resume protocol, and the registry family.  The
hypothesis property tests (batched fault draws, band permutation
invariance) skip cleanly when hypothesis isn't installed."""
import dataclasses
import json

import numpy as np
import pytest

from repro.ensemble import (AxisSpec, EnsembleSpec, lane_capable,
                            quantile_bands, run_ensemble, run_search)
from repro.ensemble.batch import BatchedFaultInjector, make_segment_fn
from repro.ensemble.engine import scalar_lane
from repro.ensemble.run import GATE_FIELDS, check_lane0
from repro.ensemble.search import SearchDriver
from repro.scenarios.registry import get_scenario, list_ensembles

SCALE, ND = 0.01, 8


def _diff(ref, got):
    return {f: (getattr(ref, f), getattr(got, f))
            for f in GATE_FIELDS if getattr(ref, f) != getattr(got, f)}


# ------------------------------------------------------------- bit identity
def test_every_lane_matches_scalar_engine():
    """The determinism contract, on all lanes of a 4-seed sweep: the lanes
    engine must replay the scalar event engine's trajectory bit-for-bit
    (iterations, float-exact sim days, fault counters, digest)."""
    espec = EnsembleSpec("t-sweep", get_scenario("paper-2022"), n_lanes=4)
    res = run_ensemble(espec, scale=SCALE, n_datasets=ND)
    assert res.engine == "lanes"
    for i, (spec, seed, label) in enumerate(espec.lane_specs()):
        ref = scalar_lane(spec, seed, label, SCALE, ND)
        assert not _diff(ref, res.lane(i)), _diff(ref, res.lane(i))


def test_lane0_gate_on_registered_ensembles():
    """The CI gate function itself, on the registered families."""
    for name in ("ensemble-paper-bands", "aimd-search"):
        espec = dataclasses.replace(get_scenario(name), n_lanes=2)
        out = check_lane0(espec, SCALE, ND, "numpy")
        assert out["match"], (name, out["mismatches"])


def test_axes_perturb_trajectories():
    """Perturbation axes must actually reach the world build: a harsher
    fault rate changes the trajectory, and labels record the axis values."""
    espec = EnsembleSpec(
        "t-axes", get_scenario("paper-2022"),
        axes=(AxisSpec("faults.transient_per_tb", (0.15, 6.0)),),
        n_lanes=2)
    res = run_ensemble(espec, scale=SCALE, n_datasets=ND)
    assert res.lane(0).label["faults.transient_per_tb"] == 0.15
    assert res.lane(1).label["faults.transient_per_tb"] == 6.0
    assert res.lane(0).faults_total < res.lane(1).faults_total
    # and each perturbed lane still replays its own scalar world exactly
    for i, (spec, seed, label) in enumerate(espec.lane_specs()):
        ref = scalar_lane(spec, seed, label, SCALE, ND)
        assert not _diff(ref, res.lane(i))


# ---------------------------------------------------------------- fallbacks
def test_federation_base_falls_back_to_scalar():
    espec = dataclasses.replace(get_scenario("seed-sweep-federation"),
                                n_lanes=2)
    ok, reason = lane_capable(espec.base)
    assert not ok and reason
    res = run_ensemble(espec, scale=0.004, n_datasets=8)
    assert res.engine == "scalar"
    assert res.lane(0).sim_days > 0
    assert res.lane(0).succeeded_digest != res.lane(1).succeeded_digest


def test_force_scalar_equals_lanes():
    espec = EnsembleSpec("t-force", get_scenario("paper-2022"), n_lanes=3)
    fast = run_ensemble(espec, scale=SCALE, n_datasets=ND)
    slow = run_ensemble(espec, scale=SCALE, n_datasets=ND,
                        force_scalar=True)
    assert fast.engine == "lanes" and slow.engine == "scalar"
    for i in range(3):
        assert not _diff(slow.lane(i), fast.lane(i))
    assert fast.bands == slow.bands


# ----------------------------------------------------------------- backends
@pytest.mark.parametrize("backend", ["jax"])
def test_segment_backends_match_reference(backend):
    """The jax segment kernel agrees with the numpy reference
    elementwise (float64 round-off only — XLA may fuse an FMA)."""
    ref_fn = make_segment_fn("numpy")
    alt_fn = make_segment_fn(backend)
    rng = np.random.default_rng(7)
    t = rng.uniform(0.0, 3600.0, size=(16, 8))
    bd = rng.uniform(0.0, 1e12, size=(16, 8))
    rate = np.where(rng.random((16, 8)) < 0.2, 0.0,
                    rng.uniform(1e6, 1e9, size=(16, 8)))
    bound = bd + rng.uniform(0.0, 1e11, size=(16, 8))
    ref = ref_fn(t, bd, rate, bound)
    alt = alt_fn(t, bd, rate, bound)
    for r, a, name in zip(ref, alt,
                          ("t_left", "new_bytes", "adv", "moved", "hit")):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(r, np.float64),
                                   rtol=1e-12, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("backend", ["jax"])
def test_lanes_engine_runs_on_accelerated_backends(backend):
    """Whole-trajectory check: the accelerated backend completes the campaign
    with the same terminal replica state as the reference (byte counts are
    integers — immune to FMA contraction — while iteration counts and
    float sim-days may drift)."""
    espec = EnsembleSpec("t-backend", get_scenario("paper-2022"), n_lanes=2)
    ref = run_ensemble(espec, scale=SCALE, n_datasets=ND)
    alt = run_ensemble(espec, scale=SCALE, n_datasets=ND, backend=backend)
    assert alt.engine == "lanes" and alt.backend == backend
    for i in range(2):
        assert alt.lane(i).bytes_at == ref.lane(i).bytes_at
        assert alt.lane(i).quarantined == ref.lane(i).quarantined
        assert not alt.lane(i).timed_out


# ------------------------------------------------------------------- search
def test_search_checkpoint_resume(tmp_path):
    ckpt = str(tmp_path / "search.json")
    espec = EnsembleSpec("t-search", get_scenario("paper-2022"), n_lanes=6)
    kw = dict(scale=SCALE, n_datasets=ND, chunk=2)
    full = run_search(espec, **kw)

    driver = SearchDriver(espec, checkpoint=ckpt, **kw)
    partial = driver.run()
    assert partial.rows == full.rows
    # truncate the checkpoint to 3 lanes and resume: lanes 0-2 come from
    # the file, 3-5 re-run, and the outcome is identical
    state = json.load(open(ckpt))
    state["done"] = state["done"][:3]
    json.dump(state, open(ckpt, "w"))
    resumed = SearchDriver(espec, checkpoint=ckpt, **kw).run()
    assert resumed.rows == full.rows
    assert resumed.winner == full.winner
    assert resumed.bands == full.bands
    # a stale checkpoint (different ensemble) is ignored, not merged
    state["name"] = "something-else"
    json.dump(state, open(ckpt, "w"))
    fresh = SearchDriver(espec, checkpoint=ckpt, **kw).run()
    assert fresh.rows == full.rows


def test_search_winner_and_bench_entry():
    espec = EnsembleSpec(
        "t-objective", get_scenario("paper-2022"),
        axes=(AxisSpec("faults.transient_per_tb", (0.15, 6.0)),),
        n_lanes=2)
    out = run_search(espec, scale=SCALE, n_datasets=ND,
                     objective="faults_total")
    assert out.winner["lane"] == 0          # fewer faults at the low rate
    entry = out.bench_entry()
    assert entry["ensemble_t-objective_faults_total"] == float(
        out.winner["faults_total"])
    ranked = out.ranking()
    assert ranked[0] == out.winner


# ----------------------------------------------------------------- registry
def test_registry_family():
    names = list_ensembles()
    for name in ("ensemble-paper-bands", "aimd-search",
                 "seed-sweep-federation"):
        assert name in names
        spec = get_scenario(name)
        assert isinstance(spec, EnsembleSpec)
    assert get_scenario("ensemble-paper-bands").n_lanes == 256
    assert get_scenario("aimd-search").n_lanes == 27


# ------------------------------------------------------- property (hypothesis)
def test_batched_fault_draws_match_solo_streams_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seeds=st.lists(st.integers(0, 2**31 - 1), min_size=1,
                          max_size=8),
           nbytes=st.lists(st.integers(1, 10**13), min_size=1, max_size=8),
           rate=st.floats(0.1, 20.0))
    def prop(seeds, nbytes, rate):
        from repro.core.faults import FaultInjector
        n = min(len(seeds), len(nbytes))
        seeds, nbytes = seeds[:n], nbytes[:n]
        paths = [f"/css/ds-{i}" for i in range(n)]
        batched = BatchedFaultInjector(seeds, transient_per_tb=rate)
        marks, lens = batched.transient_marks(paths, nbytes)
        solo = [FaultInjector(s, transient_per_tb=rate)
                .transient_marks(p, b)
                for s, p, b in zip(seeds, paths, nbytes)]
        for l in range(n):
            assert lens[l] == len(solo[l])
            assert list(marks[l, :lens[l]]) == solo[l]
            assert np.all(np.isinf(marks[l, lens[l]:]))

    prop()


def test_quantile_bands_permutation_invariant_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(vals=st.lists(st.floats(0.0, 1e4, allow_nan=False),
                         min_size=1, max_size=40),
           seed=st.integers(0, 2**16))
    def prop(vals, seed):
        rows = [{"sim_days": v, "faults_total": i}
                for i, v in enumerate(vals)]
        perm = list(rows)
        np.random.default_rng(seed).shuffle(perm)
        metrics = ("sim_days", "faults_total")
        assert (quantile_bands(rows, metrics=metrics)
                == quantile_bands(perm, metrics=metrics))

    prop()
