"""Tests for the paper's replication state machine (Figure 4), transports,
pause handling, faults, integrity, dashboard, and incremental replication."""
import os

import numpy as np
import pytest

from repro.core.campaign import CampaignConfig, run_campaign
from repro.core.dashboard import render_text, snapshot
from repro.core.faults import FaultInjector, Notifier, RetryPolicy
from repro.core.incremental import IncrementalReplicator, PublishFeed
from repro.core.pause import DAY, PauseManager
from repro.core.routes import (GB, Dataset, Route, RouteGraph, Site,
                               make_catalog, paper_route_graph,
                               split_oversized)
from repro.core.scheduler import ReplicationPolicy, ReplicationScheduler
from repro.core.transfer_table import Status, TransferTable, TransferRecord
from repro.core.transport import (LocalFSTransport, SimClock,
                                  SimulatedTransport)


def small_world(n_datasets=12, seed=0, unreadable=()):
    graph = paper_route_graph()
    catalog = {}
    for i, ds in enumerate(make_catalog(n_datasets, total_bytes=n_datasets * GB,
                                        total_files=n_datasets * 100,
                                        total_dirs=n_datasets * 10, seed=seed)):
        ds.unreadable = i in unreadable
        catalog[ds.path] = ds
    clock = SimClock()
    pause = PauseManager()
    injector = FaultInjector(seed=seed)
    notifier = Notifier()
    retry = RetryPolicy(max_retries=3, backoff_s=60.0)
    transport = SimulatedTransport(graph, clock, pause, injector, notifier, retry)
    table = TransferTable()
    sched = ReplicationScheduler(table, transport, catalog,
                                 ReplicationPolicy("LLNL", ("ALCF", "OLCF")),
                                 retry, notifier)
    sched.populate()
    return graph, catalog, clock, pause, transport, table, sched, notifier


def drive(clock, transport, sched, days=30.0, dt=600.0):
    while clock.now < days * DAY:
        sched.step(clock.now)
        clock.advance(dt)
        transport.tick()
        if sched.done():
            return True
    return sched.done()


# ------------------------------------------------------------- table basics
def test_table_populate_two_rows_per_dataset():
    t = TransferTable()
    n = t.populate(["a", "b", "c"], "LLNL", ["ALCF", "OLCF"])
    assert n == 6
    assert t.count_status(Status.NULL) == 6
    assert not t.done()


def test_table_update_and_done():
    t = TransferTable()
    t.populate(["a"], "LLNL", ["ALCF"])
    t.update("a", "ALCF", status=Status.SUCCEEDED, bytes_transferred=10)
    assert t.done()
    rec = t.get("a", "ALCF")
    assert rec.status == Status.SUCCEEDED and rec.bytes_transferred == 10


# --------------------------------------------------------- scheduler basics
def test_concurrency_cap_two_per_route():
    _, _, clock, _, transport, table, sched, _ = small_world(10)
    sched.step(clock.now)
    assert table.count_route("LLNL", "ALCF", Status.ACTIVE) == 2
    # OLCF direct transfers only start when ALCF is paused
    assert table.count_route("LLNL", "OLCF", Status.ACTIVE) == 0


def test_full_replication_completes_everywhere():
    _, catalog, clock, _, transport, table, sched, _ = small_world(10)
    assert drive(clock, transport, sched, days=40)
    for ds in catalog:
        for dst in ("ALCF", "OLCF"):
            assert table.get(ds, dst).status == Status.SUCCEEDED


def test_relay_preferred_over_slow_source():
    """Most OLCF copies must arrive via the ALCF relay, not from LLNL
    (the paper's C2: read the slow source once)."""
    _, _, clock, _, transport, table, sched, _ = small_world(16)
    assert drive(clock, transport, sched, days=60)
    via_relay = sum(1 for r in table.all()
                    if r.destination == "OLCF" and r.source == "ALCF")
    via_llnl = sum(1 for r in table.all()
                   if r.destination == "OLCF" and r.source == "LLNL")
    assert via_relay > via_llnl


def test_pause_reroutes_to_secondary():
    """While ALCF is in maintenance, LLNL->OLCF transfers must start (2c)."""
    _, _, clock, pause, transport, table, sched, _ = small_world(10)
    # get some ALCF transfers running, then pause ALCF
    sched.step(clock.now)
    clock.advance(600)
    transport.tick()
    pause.add_window("ALCF", clock.now, clock.now + 2 * DAY)
    for _ in range(10):
        sched.step(clock.now)
        clock.advance(600)
        transport.tick()
    assert table.count_route("LLNL", "OLCF",
                             Status.ACTIVE, Status.SUCCEEDED) > 0
    # paused transfers were not lost
    assert table.count_status(Status.PAUSED) >= 0
    assert drive(clock, transport, sched, days=40)


def test_persistent_fault_quarantines_then_recovers_after_fix():
    _, catalog, clock, _, transport, table, sched, notifier = small_world(
        6, unreadable=(1,))
    bad = [p for p, d in catalog.items() if d.unreadable][0]
    # run a while: the unreadable dataset should fail and notify
    drive(clock, transport, sched, days=10)
    assert any(bad in n for n in notifier.notifications)
    # human fixes it; replication completes
    notifier.fix(bad)
    assert drive(clock, transport, sched, days=60)
    assert table.get(bad, "ALCF").status == Status.SUCCEEDED


def test_oversized_scan_split():
    ds = Dataset("/big", bytes=10 * GB, files=10_000_000, directories=100)
    parts = split_oversized(ds, scan_limit_files=3_000_000)
    assert len(parts) == 4
    assert sum(p.files for p in parts) <= ds.files
    assert all(p.files <= 3_000_000 for p in parts)


# ------------------------------------------------------------- local FS
def test_localfs_transport_moves_and_verifies(tmp_path):
    root = str(tmp_path)
    src = os.path.join(root, "A", "data", "set1")
    os.makedirs(os.path.join(src, "sub"))
    rng = np.random.default_rng(0)
    for i, p in enumerate(["f0.bin", "sub/f1.bin"]):
        with open(os.path.join(src, p), "wb") as f:
            f.write(rng.bytes(1000 + i))
    tr = LocalFSTransport(root)
    uid = tr.submit(Dataset("data/set1", 2001, 2, 2), "A", "B")
    st = tr.poll(uid)
    assert st.status == Status.SUCCEEDED
    assert st.files_done == 2 and st.faults == 0
    with open(os.path.join(root, "B", "data", "set1", "f0.bin"), "rb") as f:
        got = f.read()
    with open(os.path.join(src, "f0.bin"), "rb") as f:
        want = f.read()
    assert got == want


def test_localfs_transport_detects_and_retransmits_corruption(tmp_path):
    root = str(tmp_path)
    src = os.path.join(root, "A", "ds")
    os.makedirs(src)
    with open(os.path.join(src, "f.bin"), "wb") as f:
        f.write(b"payload" * 100)
    flips = {"n": 0}

    def corruptor(path, data):
        if flips["n"] == 0:          # corrupt only the first attempt
            flips["n"] += 1
            return data[:-1] + bytes([data[-1] ^ 1])
        return data

    tr = LocalFSTransport(root, corruptor=corruptor)
    uid = tr.submit(Dataset("ds", 700, 1, 1), "A", "B")
    st = tr.poll(uid)
    assert st.status == Status.SUCCEEDED
    assert st.faults == 1            # one integrity fault, then retransmit
    with open(os.path.join(root, "B", "ds", "f.bin"), "rb") as f:
        assert f.read() == b"payload" * 100


def _many_files(root, n=48, seed=0):
    """``n`` files under ``root/A/many`` in nested directories: empty, 1 and 3
    bytes, sizes that are not a multiple of 4, and two over a copy chunk;
    returns their paths relative to the dataset, largest first."""
    from repro.core.transport import _CHUNK_BYTES
    rng = np.random.default_rng(seed)
    sizes = [0, 1, 3, 4097, _CHUNK_BYTES + 3, 2 * _CHUNK_BYTES + 1]
    sizes += [int(x) for x in rng.integers(5, 300_000, n - len(sizes))]
    rels = []
    for i, size in enumerate(sizes):
        rel = os.path.join(*[f"d{i % k}" for k in (2, 3, 5)][:i % 4], f"f{i}.bin")
        path = os.path.join(root, "A", "many", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(rng.bytes(size))
        rels.append(rel)
    ds = Dataset("many", sum(sizes), n, 1)
    return ds, [r for _, r in sorted(zip(sizes, rels), reverse=True)]


def _same_bytes(root, rel, site="B", dataset="many"):
    with open(os.path.join(root, "A", dataset, rel), "rb") as f:
        want = f.read()
    with open(os.path.join(root, site, dataset, rel), "rb") as f:
        return f.read() == want


def _threads_of_moves(monkeypatch):
    """Record the thread that moves each file."""
    import threading
    seen = []
    move = LocalFSTransport._move_file

    def spy(self, sp, dp):
        seen.append(threading.get_ident())
        return move(self, sp, dp)

    monkeypatch.setattr(LocalFSTransport, "_move_file", spy)
    return seen


def _pool_threads():
    import threading
    return [t for t in threading.enumerate() if t.name.startswith("transport")]


def test_localfs_transport_pool_moves_many_files(tmp_path, monkeypatch):
    """Every file byte-equal, moved on pool threads whose copy and verify
    spans keep the transfer's span as their parent."""
    import threading

    from repro.obs import spans
    rec = spans.Recorder()
    monkeypatch.setattr(spans, "RECORDER", rec)
    root = str(tmp_path)
    ds, rels = _many_files(root)
    seen = _threads_of_moves(monkeypatch)
    tr = LocalFSTransport(root)
    st = tr.poll(tr.submit(ds, "A", "B"))
    assert st.status == Status.SUCCEEDED
    assert (st.files_done, st.bytes_done, st.faults) == (len(rels), ds.bytes, 0)
    assert all(_same_bytes(root, r) for r in rels)
    assert len(seen) == len(rels)
    assert len(set(seen)) > 1 and threading.get_ident() not in seen
    assert not _pool_threads()
    (submit,) = [r for r in rec.records() if r.name == "transport.submit"]
    for name in ("transport.copy", "transport.verify"):
        mine = [r for r in rec.records() if r.name == name]
        assert len(mine) == len(rels)
        assert all(r.parent == submit.id for r in mine)
        assert sum(r.attrs["bytes"] for r in mine) == ds.bytes
    assert rec.totals()["transport.submit"].self_seconds == pytest.approx(
        submit.seconds, abs=1e-12)


def test_localfs_transport_pool_retransmits_first_attempts(tmp_path,
                                                           monkeypatch):
    """A corruptor keyed by path flips a byte in the first attempt of every
    third file, with more workers than cores and the interpreter switching
    threads as often as it can: each hit costs one fault and one
    retransmission, and the corruptor, which keeps no lock of its own, is
    called once per chunk of every attempt."""
    import sys
    import time

    import repro.core.transport as transport
    root = str(tmp_path)
    monkeypatch.setattr(transport, "_CHUNK_BYTES", 4096)
    monkeypatch.setattr(transport, "_MAX_WORKERS", 64)
    monkeypatch.setattr(transport.os, "cpu_count", lambda: 64)
    ds, rels = _many_files(root, n=60, seed=1)
    sizes = {r: os.path.getsize(os.path.join(root, "A", "many", r))
             for r in rels}
    hit = {os.path.join(root, "A", "many", r)
           for r in sorted(rels)[::3] if sizes[r]}
    flipped, calls = set(), {"n": 0}

    def corruptor(path, data):
        n = calls["n"]
        time.sleep(0)                         # another worker may run here
        calls["n"] = n + 1                    # and its update be lost
        if path in hit and path not in flipped:
            flipped.add(path)
            return bytes([data[0] ^ 0xFF]) + data[1:]
        return data

    seen = _threads_of_moves(monkeypatch)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        tr = LocalFSTransport(root, corruptor=corruptor)
        st = tr.poll(tr.submit(ds, "A", "B"))
    finally:
        sys.setswitchinterval(old)
    chunks = {p: -(-sizes[r] // 4096)
              for r in rels for p in [os.path.join(root, "A", "many", r)]}
    assert st.status == Status.SUCCEEDED
    assert flipped == hit and st.faults == len(hit) > 10
    assert (st.files_done, st.bytes_done) == (len(rels), ds.bytes)
    assert calls["n"] == sum(chunks.values()) + sum(chunks[p] for p in hit)
    assert all(_same_bytes(root, r) for r in rels)
    assert len(set(seen)) > 1
    assert not _pool_threads()


def test_localfs_transport_pool_fails_on_persistent_corruption(tmp_path):
    """One file among many is corrupted on every attempt: the transfer
    fails, counts what finished, and leaves no worker running."""
    root = str(tmp_path)
    ds, rels = _many_files(root)
    bad = os.path.join(root, "A", "many", rels[len(rels) // 2])

    def corruptor(path, data):
        return data[:-1] + bytes([data[-1] ^ 1]) if path == bad else data

    tr = LocalFSTransport(root, corruptor=corruptor)
    st = tr.poll(tr.submit(ds, "A", "B"))
    assert not _pool_threads()
    assert st.status == Status.FAILED
    assert "persistent corruption" in st.detail and bad in st.detail
    assert st.faults == 3 + 1                 # three attempts, then the failure
    assert st.files_done < len(rels) and st.bytes_done < ds.bytes
    assert not _same_bytes(root, rels[len(rels) // 2])


def test_localfs_transport_one_file_opens_no_pool(tmp_path, monkeypatch):
    import threading

    import repro.core.transport as transport

    def no_pool(*a, **kw):
        raise AssertionError("a one-file transfer opened a pool")

    monkeypatch.setattr(transport, "ThreadPoolExecutor", no_pool)
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "A", "one"))
    data = np.random.default_rng(2).bytes(3 * transport._CHUNK_BYTES + 1)
    with open(os.path.join(root, "A", "one", "f.bin"), "wb") as f:
        f.write(data)
    seen = _threads_of_moves(monkeypatch)
    tr = LocalFSTransport(root)
    st = tr.poll(tr.submit(Dataset("one", len(data), 1, 1), "A", "B"))
    assert st.status == Status.SUCCEEDED and st.bytes_done == len(data)
    assert seen == [threading.get_ident()]
    assert _same_bytes(root, "f.bin", dataset="one")


# -------------------------------------------------------------- incremental
def test_incremental_replication_picks_up_new_datasets():
    _, catalog, clock, _, transport, table, sched, _ = small_world(4)
    feed = PublishFeed()
    inc = IncrementalReplicator(feed, sched, check_interval=DAY)
    drive(clock, transport, sched, days=20)
    assert sched.done()
    new = Dataset("/css03_data/CMIP6/NEW/late-dataset", 2 * GB, 100, 10)
    feed.publish(clock.now + 1, new)
    clock.advance(2 * DAY)
    added = inc.maybe_check(clock.now)
    assert new.path in added
    assert not sched.done()
    assert drive(clock, transport, sched, days=60)
    assert table.get(new.path, "OLCF").status == Status.SUCCEEDED


# ---------------------------------------------------------------- dashboard
def test_dashboard_renders():
    _, catalog, clock, _, transport, table, sched, _ = small_world(6)
    for _ in range(5):
        sched.step(clock.now)
        clock.advance(600)
        transport.tick()
    total = sum(d.bytes for d in catalog.values())
    txt = render_text(table, ["ALCF", "OLCF"], total, clock.now)
    assert "Replication to ALCF" in txt and "Replication to OLCF" in txt
    snap = snapshot(table, ["ALCF", "OLCF"], total, clock.now)
    assert set(snap["destinations"]) == {"ALCF", "OLCF"}


# ----------------------------------------------------------------- campaign
def test_reduced_campaign_completes_and_relays():
    cfg = CampaignConfig(n_datasets=60, scale=0.02, step_s=3600.0,
                         max_days=200, seed=1)
    rep = run_campaign(cfg)
    assert rep.bytes_at["ALCF"] == rep.total_bytes
    assert rep.bytes_at["OLCF"] == rep.total_bytes
    assert rep.duration_days < 200
    assert rep.duration_days > rep.floor_days   # physics: can't beat the floor
    # relay route carried traffic
    assert ("ALCF", "OLCF") in rep.per_route_transfers
    # fault skew: max >> mean (paper Fig. 6)
    if rep.faults_total:
        assert rep.faults_per_transfer_max >= rep.faults_per_transfer_mean
