"""Flight-recorder tests: the obs-on/obs-off bit-identity contract (single
campaign, federation, scrub), cross-process NDJSON byte identity, snapshot
byte identity, trace ring budgeting, metrics registry semantics, transport
flow-telemetry horizon pruning, dashboard JSON cleanliness, the phase
profiler, the post-mortem report CLI, and the program span recorder (its
tree, self time and ring, the spans of a replicated save, and their place
on the profiler's host plane)."""
import dataclasses
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from repro.core.snapshot import (federation_trajectory_summary,
                                 trajectory_summary)
from repro.obs import FULL_OBS, NO_OBS, ObsSpec
from repro.obs.metrics import Counter, Histogram, MetricsRegistry
from repro.obs.sink import ObsSink, json_line, sanitize
from repro.obs.trace import TraceRecorder, to_chrome
from repro.scenarios.events import EngineStats, run_world
from repro.scenarios.registry import get_scenario, scenario_tags

TINY = dict(n_datasets=12, scale=0.01)


def _cli_env():
    return dict(os.environ, PYTHONPATH="src" + os.pathsep +
                os.environ.get("PYTHONPATH", ""))


def _run_traj(spec, **kw):
    world = spec.build(**kw)
    stats = EngineStats()
    rep = run_world(world, engine="events", stats=stats)
    return world, trajectory_summary(rep, stats, world.table)


# ================================================== bit-identity contract
@pytest.mark.parametrize("name", ["paper-2022", "scrub-and-repair",
                                  "esgf-serving"])
def test_obs_on_off_trajectory_identical(name):
    spec = get_scenario(name)
    _, off = _run_traj(spec.with_obs(NO_OBS), **TINY)
    world, on = _run_traj(spec.with_obs(FULL_OBS), **TINY)
    assert on == off
    # and the recorder actually saw the campaign it did not perturb
    assert world.obs is not None
    assert world.obs.trace.summary()["events"] > 0
    assert len(world.obs.samples) >= 2


def test_obs_on_off_federation_identical():
    fed = get_scenario("federation-paper-twice")
    kw = dict(n_datasets=8, scale=0.004)

    def run(spec):
        world = spec.build(**kw)
        stats = EngineStats()
        rep = run_world(world, engine="events", stats=stats)
        return world, federation_trajectory_summary(rep, stats, world)

    _, off = run(fed)
    world, on = run(fed.with_obs(FULL_OBS))
    assert on == off
    # every member carries its own recorder, labelled by campaign
    labels = [rt.obs.label for rt in world.runtimes]
    assert len(labels) == 2 and len(set(labels)) == 2
    for rt in world.runtimes:
        assert rt.obs.trace.summary()["events"] > 0


def test_strict_cadence_keeps_physical_trajectory():
    spec = get_scenario("paper-2022")
    _, off = _run_traj(spec, **TINY)
    world, on = _run_traj(
        spec.with_obs(ObsSpec(metrics=True, strict_cadence=True,
                              sample_interval_days=1.0)), **TINY)
    # extra sampling iterations are allowed; the physics must not move
    for key in ("faults_total", "quarantined", "bytes_at",
                "succeeded_digest"):
        assert on[key] == off[key]
    assert on["iterations"] >= off["iterations"]
    # strict cadence means samples land on (near-)exact day boundaries
    days = [s["t_day"] for s in world.obs.samples[1:-1]]
    assert days, "no interior samples taken"
    for d in days:
        assert abs(d - round(d)) < 1e-3


# ================================================ cross-process determinism
def test_ndjson_stream_byte_identical_across_processes(tmp_path):
    env = _cli_env()
    base = [sys.executable, "-m", "repro.scenarios.run", "--scenario",
            "paper-2022", "--datasets", "12", "--scale", "0.01"]
    paths = [str(tmp_path / f"run{i}.ndjson") for i in (1, 2)]
    for p in paths:
        r = subprocess.run(base + ["--obs", p], capture_output=True,
                           text=True, timeout=300, env=env, cwd=".")
        assert r.returncode == 0, r.stderr[-2000:]
    b1, b2 = (open(p, "rb").read() for p in paths)
    assert b1 == b2
    assert b1.count(b"\n") > 10


def _strip_uids(obj):
    """In-flight transfer uids are ``uuid4()`` — random per process even
    without obs — so snapshot comparison normalizes them away."""
    if isinstance(obj, dict):
        return {k: ("UID" if k == "uid" else _strip_uids(v))
                for k, v in obj.items()}
    if isinstance(obj, list):
        return [_strip_uids(v) for v in obj]
    return obj


def test_snapshot_identical_obs_on_off(tmp_path):
    """The recorder is excluded from snapshots: a mid-run checkpoint taken
    under observation equals the checkpoint of an unobserved run (modulo
    the process-random transfer uids, which differ between any two runs)."""
    env = _cli_env()
    base = [sys.executable, "-m", "repro.scenarios.run", "--scenario",
            "paper-2022", "--datasets", "12", "--scale", "0.01",
            "--kill-after", "40"]
    snaps = {}
    for arm, extra in (("off", []),
                       ("on", ["--obs", str(tmp_path / "run.ndjson")])):
        ck = str(tmp_path / f"ck-{arm}")
        r = subprocess.run(base + ["--checkpoint-dir", ck] + extra,
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=".")
        assert r.returncode == 3, (r.returncode, r.stderr[-2000:])
        latest = open(os.path.join(ck, "LATEST")).read().strip()
        assert latest == "snapshot-00000040.json"   # same kill iteration
        with open(os.path.join(ck, latest)) as f:
            snaps[arm] = _strip_uids(json.load(f))
    assert snaps["on"] == snaps["off"]


def test_obs_flag_refused_on_resume(tmp_path):
    env = _cli_env()
    r = subprocess.run([sys.executable, "-m", "repro.scenarios.run",
                        "--resume", str(tmp_path / "nope"), "--obs",
                        str(tmp_path / "x.ndjson")],
                       capture_output=True, text=True, timeout=60, env=env,
                       cwd=".")
    assert r.returncode != 0
    assert "--obs" in (r.stderr + r.stdout)


# ====================================================== trace ring + sink
def test_trace_ring_budget_evicts_oldest_but_sink_sees_all(tmp_path):
    p = str(tmp_path / "t.ndjson")
    sink = ObsSink(p)
    tr = TraceRecorder(budget_bytes=600, campaign="c", sink=sink)
    for i in range(50):
        tr.record(float(i), "dispatched", dataset=f"ds{i:04d}", dest="X")
    sink.close()
    s = tr.summary()
    assert s["events"] == 50
    assert s["dropped"] > 0 and s["retained"] < 50
    assert s["ring_bytes"] <= 600
    # ring keeps the newest records
    kept = tr.records()
    assert kept[-1]["dataset"] == "ds0049"
    # the streaming sink is unbounded: every event landed
    lines = open(p).read().splitlines()
    assert sum(1 for ln in lines if json.loads(ln)["k"] == "trace") == 50


def test_json_line_deterministic_and_nan_clean():
    obj = {"b": float("nan"), "a": float("inf"), "c": [1.0, -float("inf")],
           "d": {"y": 2, "x": 1}}
    line = json_line(obj)
    assert line == json_line(dict(reversed(list(obj.items()))))
    assert "NaN" not in line and "Infinity" not in line
    assert sanitize(float("nan")) is None


def test_to_chrome_spans_and_metadata():
    tr = TraceRecorder(budget_bytes=1 << 20, campaign="c")
    tr.record(0.0, "queued", dataset="d", dest="A")
    tr.record(10.0, "dispatched", dataset="d", dest="A")
    tr.record(25.0, "succeeded", dataset="d", dest="A")
    tr.record(30.0, "scrub-pass", scanned=4, detected=0)
    doc = to_chrome(tr.records())
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert "X" in phases and "i" in phases and "M" in phases
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    # 1 trace microsecond == 1 sim second
    assert span["ts"] == pytest.approx(10.0)
    assert span["dur"] == pytest.approx(15.0)
    assert span["name"] == "succeeded"


# ========================================================= metrics registry
def test_metrics_primitives():
    c = Counter()
    c.inc(); c.inc(3)
    assert c.value == 4
    h = Histogram()
    for v in (30.0, 90.0, 5000.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["sum"] == pytest.approx(5120.0)
    assert s["p50"] >= 30.0
    reg = MetricsRegistry()
    reg.counter("a.b").inc()
    assert reg.counter("a.b") is reg.counter("a.b")
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 1


def test_obs_spec_validation():
    with pytest.raises(ValueError):
        ObsSpec(metrics=True, sample_interval_days=0.0).validate()
    with pytest.raises(ValueError):
        ObsSpec(trace=True, trace_budget_bytes=0).validate()
    NO_OBS.validate()   # disabled spec never validates its knobs


# ================================================= flow-telemetry horizon
def test_flow_horizon_bounds_flow_totals():
    spec = get_scenario("paper-2022")
    bounded = dataclasses.replace(spec, flow_horizon_days=3.0)
    w1, t1 = _run_traj(spec, **TINY)
    w2, t2 = _run_traj(bounded, **TINY)
    # pruning is pure telemetry hygiene: the trajectory cannot move
    assert t1 == t2
    tr1 = w1.runtime.sched.transport
    tr2 = w2.runtime.sched.transport
    days1 = {k[0] for k in tr1.flow_totals}
    days2 = {k[0] for k in tr2.flow_totals}
    assert max(days1) - min(days1) > 3      # unbounded run spans the campaign
    assert max(days2) - min(days2) <= 3     # bounded run kept the horizon
    assert len(tr2.flow_totals) < len(tr1.flow_totals)


def test_federation_members_must_agree_on_flow_horizon():
    fed = get_scenario("federation-paper-twice")
    members = list(fed.members)
    members[0] = dataclasses.replace(
        members[0], scenario=dataclasses.replace(
            members[0].scenario, flow_horizon_days=5.0))
    bad = dataclasses.replace(fed, members=tuple(members))
    with pytest.raises(ValueError, match="flow_horizon_days"):
        bad.build(n_datasets=8, scale=0.004)


# ======================================================== dashboard rows
def test_dashboard_row_dict_json_clean():
    from repro.core.dashboard import row_dict
    world, _ = _run_traj(get_scenario("paper-2022").with_obs(FULL_OBS),
                         **TINY)
    rows = [row_dict(r) for r in world.table.all()]
    assert rows
    text = json.dumps(rows, allow_nan=False)     # raises on NaN/inf
    assert "NaN" not in text
    # obs rows render without touching world state
    from repro.core.dashboard import obs_rows, render_obs_text
    kinds = {r["kind"] for r in obs_rows(world.obs)}
    assert kinds == {"trace", "metrics"}
    assert "trace" in render_obs_text(world.obs, 0.0)


# ========================================================= phase profiler
def test_phase_profiler_wrap_and_restore():
    from repro.core.scheduler import ReplicationScheduler
    from repro.obs.profile import PhaseProfiler
    orig_step = ReplicationScheduler.step
    with PhaseProfiler() as prof:
        prof.instrument_standard()
        assert ReplicationScheduler.step is not orig_step
        world = get_scenario("paper-2022").build(**TINY)
        run_world(world, engine="events")
    assert ReplicationScheduler.step is orig_step
    rep = prof.report(wall_s=1.0)
    assert rep["wall_s"] == 1.0
    assert rep["phases_s"]["sched"] > 0
    assert rep["phases_s"]["driver"] >= 0
    assert sum(rep["phases_pct"].values()) == pytest.approx(100.0, abs=0.5)


# ===================================================== post-mortem report
def test_report_cli_and_perfetto_export(tmp_path):
    env = _cli_env()
    nd = str(tmp_path / "run.ndjson")
    r = subprocess.run([sys.executable, "-m", "repro.scenarios.run",
                        "--scenario", "paper-2022", "--datasets", "12",
                        "--scale", "0.01", "--obs", nd],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=".")
    assert r.returncode == 0, r.stderr[-2000:]
    from repro.obs.report import load_stream, main, render
    stream = load_stream(nd)
    assert stream["trace"] and stream["metrics"] and stream["meta"]
    text = render(stream, top=5)
    for section in ("post-mortem", "days vs bytes", "fault / outage",
                    "slowest routes", "most-retried"):
        assert section in text.lower(), f"missing section {section!r}"
    pf = str(tmp_path / "trace.json")
    assert main([nd, "--perfetto", pf, "--json"]) == 0
    doc = json.load(open(pf))
    assert doc["traceEvents"]
    assert all(set(e) >= {"ph", "ts", "pid", "tid"}
               for e in doc["traceEvents"] if e["ph"] != "M")


# ======================================================= registry + lanes
def test_harsh_faults_scenario_registered_with_obs():
    spec = get_scenario("harsh-faults")
    assert spec.obs.enabled and spec.obs.trace and spec.obs.metrics
    assert "obs" in scenario_tags(spec)
    assert any(not o.planned for o in spec.outages)


def test_lane_engine_refuses_observed_specs():
    from repro.ensemble.lanes import lane_capable
    spec = get_scenario("paper-2022")
    ok, _ = lane_capable(spec)
    assert ok
    ok, reason = lane_capable(spec.with_obs(FULL_OBS))
    assert not ok and "recorder" in reason


# ============================================================ program spans
def test_spans_nest_per_thread_with_parent_ids():
    """Two threads, each with a span inside a span, interleaved: every span's
    parent is the enclosing span on its own thread."""
    import threading

    from repro.obs.spans import Recorder
    rec = Recorder()
    opened, inner_done = threading.Barrier(2, timeout=10), threading.Barrier(
        2, timeout=10)

    def work(tag):
        with rec.span(f"outer.{tag}"):
            opened.wait()
            with rec.span(f"inner.{tag}", tag=tag):
                pass
            inner_done.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    by = {s.name: s for s in rec.records()}
    assert set(by) == {"outer.a", "outer.b", "inner.a", "inner.b"}
    for tag in "ab":
        outer, inner = by[f"outer.{tag}"], by[f"inner.{tag}"]
        assert outer.parent is None and inner.parent == outer.id
        assert outer.start <= inner.start <= inner.end <= outer.end
        assert inner.attrs == {"tag": tag}
    assert len({s.id for s in by.values()}) == 4


def test_span_self_time_is_duration_minus_children():
    import time

    from repro.obs.spans import Recorder
    rec = Recorder()
    with rec.span("parent") as s:
        for _ in range(2):
            with rec.span("child"):
                time.sleep(0.002)
        time.sleep(0.003)
        s.set(bytes=7)
    parent = next(r for r in rec.records() if r.name == "parent")
    children = [r for r in rec.records() if r.name == "child"]
    assert parent.attrs == {"bytes": 7}
    assert all(c.parent == parent.id for c in children)
    tot = rec.totals()
    assert tot["child"].count == 2
    assert tot["child"].self_seconds == tot["child"].seconds
    assert tot["parent"].seconds == pytest.approx(parent.seconds, abs=1e-12)
    assert tot["parent"].self_seconds == pytest.approx(
        parent.seconds - sum(c.seconds for c in children), abs=1e-12)
    assert 0.003 <= tot["parent"].self_seconds < parent.seconds


def test_span_ring_drops_the_oldest_and_totals_keep_all():
    from repro.obs.spans import CAPACITY, Recorder
    rec = Recorder()
    for i in range(CAPACITY + 6):
        with rec.span(f"s{i % 2}", i=i):
            pass
    kept = [r.attrs["i"] for r in rec.records()]
    assert kept == list(range(6, CAPACITY + 6))
    assert rec.dropped == 6
    tot = rec.totals()
    assert tot["s0"].count == tot["s1"].count == (CAPACITY + 6) // 2


def test_span_recorder_loses_nothing_under_many_threads():
    """More threads than cores and a short switch interval: every span is
    counted and either kept or counted as dropped."""
    import threading

    from repro.obs.spans import CAPACITY, Recorder
    rec, n_threads = Recorder(), 4 * (os.cpu_count() or 2)
    per = CAPACITY // n_threads        # twice the ring: half is dropped
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with rec.span("outer"):
                    with rec.span("inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    total = 2 * n_threads * per
    tot = rec.totals()
    assert tot["outer"].count == tot["inner"].count == n_threads * per
    assert len(rec.records()) + rec.dropped == total
    assert len({r.id for r in rec.records()}) == len(rec.records())
    recs = {r.id: r for r in rec.records()}
    assert rec.dropped > 0
    for r in recs.values():
        if r.name == "inner" and r.parent in recs:
            assert recs[r.parent].name == "outer"


def _adopted_child(rec, parent, hold=0.02):
    """A span ``child`` opened on a new thread under ``parent``, then a
    ``loose`` span on the same thread after the adoption ends."""
    import threading
    import time

    from repro.obs import spans

    def work():
        with spans.adopt(parent):
            with rec.span("child", k=1):
                with rec.span("grandchild"):
                    time.sleep(hold)
        with rec.span("loose"):
            pass

    t = threading.Thread(target=work)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()


def test_adopted_span_carries_its_parents_id():
    from repro.obs.spans import Recorder
    rec = Recorder()
    with rec.span("parent") as p:
        _adopted_child(rec, p)
    by = {r.name: r for r in rec.records()}
    assert by["parent"].parent is None
    assert by["child"].parent == by["parent"].id and by["child"].attrs == {"k": 1}
    assert by["grandchild"].parent == by["child"].id
    assert by["loose"].parent is None            # the adoption has ended
    assert by["parent"].start <= by["child"].start <= by["child"].end \
        <= by["parent"].end


def test_adopted_span_leaves_its_parents_self_time():
    """A child on another thread overlaps its parent in wall time: the
    parent's self time stays its whole duration, while the child's own
    child still counts against the child."""
    import time

    from repro.obs.spans import Recorder
    rec = Recorder()
    with rec.span("parent") as p:
        _adopted_child(rec, p, hold=0.03)
        time.sleep(0.002)
    by = {r.name: r for r in rec.records()}
    tot = rec.totals()
    assert tot["parent"].self_seconds == pytest.approx(by["parent"].seconds,
                                                       abs=1e-12)
    assert tot["parent"].self_seconds >= 0.03
    assert tot["child"].self_seconds == pytest.approx(
        by["child"].seconds - by["grandchild"].seconds, abs=1e-12)


def test_adopted_spans_are_counted_in_the_totals():
    from repro.obs.spans import Recorder
    rec = Recorder()
    with rec.span("parent") as p:
        for _ in range(3):
            _adopted_child(rec, p, hold=0.001)
    children = [r for r in rec.records() if r.name == "child"]
    tot = rec.totals()
    assert tot["child"].count == 3 and tot["grandchild"].count == 3
    assert tot["loose"].count == 3 and tot["parent"].count == 1
    assert tot["child"].seconds == pytest.approx(
        sum(c.seconds for c in children), abs=1e-12)
    assert {c.parent for c in children} == {p.id}


def test_spans_import_and_record_without_loading_jax():
    """Host-only processes (the simulator's workers, a replication without
    a training job) record spans without loading jax."""
    code = ("import sys; sys.path.insert(0, 'src'); "
            "from repro.obs import spans; import repro.core.transport\n"
            "with spans.span('x', bytes=1) as s: s.set(files=1)\n"
            "print('jax' in sys.modules, spans.totals()['x'].count)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split() == ["False", "1"]


def _replicated_save(root, tree, step=8):
    """One save of ``tree`` at POD0, replicated to POD1 and STORE."""
    from repro.checkpoint.ckpt import save_checkpoint
    from repro.checkpoint.replicate import CheckpointReplicator
    rep = CheckpointReplicator(str(root))
    d = save_checkpoint(os.path.join(rep.site_dir("POD0"), "ckpts"), step, tree)
    assert rep.replicate(os.path.relpath(d, rep.site_dir("POD0")))
    return rep, d


def _tree(n):
    import jax.numpy as jnp
    import numpy as np
    return {"w": jnp.asarray(np.arange(n * n, dtype=np.float32).reshape(n, n)),
            "b": jnp.ones((n, n // 2), jnp.bfloat16),
            "step": jnp.int32(3)}


def _dir_bytes(d, skip=()):
    return sum(os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)
               if f not in skip)


def test_span_tree_of_one_replicated_save(tmp_path, monkeypatch):
    """One save replicated from one source to two replicas: the tree of
    spans, one copy and one verification per file and destination, byte
    attributes that add up to the files on disk, and five hash passes."""
    import numpy as np

    from repro.obs import spans
    rec = spans.Recorder()
    monkeypatch.setattr(spans, "RECORDER", rec)
    tree = _tree(1024)
    rep, d = _replicated_save(tmp_path, tree)
    recs = rec.records()
    by_id = {r.id: r for r in recs}

    def named(name):
        return [r for r in recs if r.name == name]

    (save,), (repl,) = named("ckpt.save"), named("ckpt.replicate")
    files = sorted(os.listdir(d))
    manifest_skip = ("MANIFEST.json", "COMMITTED")
    state_bytes = sum(np.asarray(x).nbytes for x in tree.values())
    # the save
    assert save.parent is None and save.attrs["step"] == 8
    assert len(named("ckpt.device_get")) == len(tree)
    assert sum(r.attrs["bytes"] for r in named("ckpt.device_get")) == state_bytes
    for name in ("ckpt.device_get", "ckpt.write", "ckpt.manifest"):
        assert all(by_id[r.parent] is save for r in named(name))
    (manifest,) = named("ckpt.manifest")
    written = sum(r.attrs["bytes"] for r in named("ckpt.write"))
    assert written == manifest.attrs["bytes"] == save.attrs["bytes"] \
        == _dir_bytes(d, manifest_skip)
    assert sum(r.attrs["files"] for r in named("ckpt.write")) \
        == save.attrs["files"] == len(files) - len(manifest_skip)
    # the replication: one submit per replica, one copy and one verify per
    # file of each
    submits = named("transport.submit")
    assert sorted(s.attrs["dest"] for s in submits) == ["POD1", "STORE"]
    assert all(by_id[s.parent] is repl for s in submits)
    assert repl.attrs == {"bytes": _dir_bytes(d), "files": len(files)}
    for s in submits:
        dest = os.path.join(rep.site_dir(s.attrs["dest"]),
                            os.path.relpath(d, rep.site_dir("POD0")))
        assert sorted(os.listdir(dest)) == files
        for name in ("transport.copy", "transport.verify"):
            mine = [r for r in named(name) if r.parent == s.id]
            assert len(mine) == len(files)
            assert sum(r.attrs["bytes"] for r in mine) == _dir_bytes(dest)
        assert s.attrs == {"dest": s.attrs["dest"], "bytes": _dir_bytes(dest),
                           "files": len(files), "faults": 0}
    hashed = sum(r.attrs["bytes"] for r in recs if r.name in (
        "ckpt.manifest", "transport.copy", "transport.verify"))
    assert hashed / state_bytes == pytest.approx(5.0, abs=0.01)
    # self time: what the scheduler and the table cost inside replicate
    tot = rec.totals()
    assert tot["ckpt.replicate"].self_seconds == pytest.approx(
        repl.seconds - sum(s.seconds for s in submits), abs=1e-9)


def test_program_spans_land_on_the_profilers_host_plane(tmp_path,
                                                         monkeypatch):
    """Under a running ``jax.profiler`` trace, a save, its replication and
    a restore put every program span on the trace's ``/host:CPU`` plane,
    nested as recorded, with the recorder's durations and offsets within a
    millisecond: the spans are on the trace's clock."""
    import glob

    import jax

    from repro.obs import spans
    rec = spans.Recorder()
    monkeypatch.setattr(spans, "RECORDER", rec)
    tree = _tree(64)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        rep, _ = _replicated_save(tmp_path / "sites", tree)
        shutil.rmtree(rep.site_dir("POD0"))
        got = rep.restore_anywhere("ckpts", tree, step=8)
    finally:
        jax.profiler.stop_trace()
    assert got is not None and got[3] == "POD1"
    recs = sorted(rec.records(), key=lambda r: r.start)
    names = {r.name for r in recs}
    assert {"ckpt.save", "ckpt.replicate", "transport.copy",
            "ckpt.restore", "ckpt.verify", "ckpt.read"} <= names
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    events = sorted(((ev.start_ns, ev.end_ns, ev.name) for line in host.lines
                     for ev in line.events if ev.name in names))
    assert [e[2] for e in events] == [r.name for r in recs]
    ev_of = {r.id: e for r, e in zip(recs, events)}
    t0_ev, t0_rec = events[0][0], recs[0].start
    for r in recs:
        a, b, _ = ev_of[r.id]
        assert abs((b - a) / 1e9 - r.seconds) < 1e-3, r
        assert abs((a - t0_ev) / 1e9 - (r.start - t0_rec)) < 1e-3, r
        if r.parent is not None:
            pa, pb, _ = ev_of[r.parent]
            assert pa <= a and b <= pb, r
