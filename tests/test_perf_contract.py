"""The O(active) performance contract.

Per-iteration work in the simulation hot path must scale with *live*
transfers (bounded by max_active_per_route × routes), never with catalog
size: transport polls, table rows materialized per step, the live transfer
pool, and telemetry growth are all asserted here, plus the behavioral
guarantees the optimizations must preserve (vectorized == scalar mover,
cache == database, streamed == whole-buffer checksums).
"""
import dataclasses
import sys
import threading

import numpy as np
import pytest

from repro.core.integrity import StreamingChecksum, file_checksum
from repro.core.pause import DAY
from repro.core.transfer_table import Status, TransferTable
from repro.scenarios.events import EngineStats, run_world
from repro.scenarios.registry import get_scenario


def _instrumented_run(n_datasets, scale=0.02, seed=0):
    """Run paper-2022 under the event engine counting, per iteration, the
    transport polls issued and the table rows materialized by ``by_status``."""
    world = get_scenario("paper-2022").build(scale=scale, seed=seed,
                                             n_datasets=n_datasets)
    counts = {"polls": 0, "rows": 0, "live_max": 0}
    orig_poll = world.transport.poll

    def poll(uid):
        counts["polls"] += 1
        return orig_poll(uid)

    orig_by_status = world.table.by_status

    def by_status(*a, **kw):
        rows = orig_by_status(*a, **kw)
        counts["rows"] += len(rows)
        return rows

    world.transport.poll = poll
    world.table.by_status = by_status

    def observer(world, now):
        counts["live_max"] = max(counts["live_max"],
                                 world.transport.live_count)

    stats = EngineStats()
    run_world(world, engine="events", stats=stats, on_iteration=observer)
    return counts, stats, world


# --------------------------------------------------------- O(active) contract
def test_per_iteration_work_scales_with_live_not_catalog():
    """4x the catalog must not change per-iteration poll counts or row
    volume: both are bounded by the live-transfer pool (≤ 2 per route)."""
    small_counts, small_stats, small_world = _instrumented_run(60)
    big_counts, big_stats, big_world = _instrumented_run(240)
    max_live = (small_world.spec.max_active_per_route
                * len(small_world.graph.routes))

    for counts, stats in ((small_counts, small_stats),
                          (big_counts, big_stats)):
        assert counts["live_max"] <= max_live
        polls_per_iter = counts["polls"] / stats.iterations
        rows_per_iter = counts["rows"] / stats.iterations
        # _poll touches each live row once; re-admission & pause checks may
        # re-materialize a handful more — but never the catalog
        assert polls_per_iter <= max_live
        assert rows_per_iter <= 4 * max_live
    small_rate = small_counts["rows"] / small_stats.iterations
    big_rate = big_counts["rows"] / big_stats.iterations
    assert big_rate <= 1.5 * small_rate + 5.0


def test_terminal_transfers_evicted_from_live_pool():
    """Finished transfers leave the live pool (tick/poll/next_event_hint
    never touch them again) but their final state stays pollable."""
    counts, stats, world = _instrumented_run(24)
    assert world.sched.done()
    assert world.transport.live_count == 0
    rec = world.table.by_status(Status.SUCCEEDED)[0]
    st = world.transport.poll(rec.uuid)        # archived, still answers
    assert st.status == Status.SUCCEEDED
    assert st.bytes_done > 0
    assert world.transport.next_event_hint() == float("inf")


def test_flow_telemetry_bounded_by_days_times_routes():
    """Satellite: flow telemetry aggregates per (day, route) — its size is
    bounded by the calendar, not by movers × ticks."""
    _, _, world = _instrumented_run(60)
    flows = world.transport.flow_totals
    assert flows
    days = world.clock.now / DAY
    assert len(flows) <= (int(days) + 1) * len(world.graph.routes)
    for (day, route), nbytes in flows.items():
        assert isinstance(day, int)
        assert route in world.graph.routes
        assert nbytes > 0
    # every byte that landed anywhere is accounted for in the flow telemetry
    total_flow = sum(flows.values())
    total_landed = sum(world.table.bytes_at(r)
                       for r in world.spec.replicas)
    assert total_flow == pytest.approx(total_landed, rel=1e-6)


# ------------------------------------------------- vectorized mover fidelity
def test_vectorized_mover_matches_scalar_exactly():
    """The SoA fast path mirrors the segment-exact scalar walk operation-for-
    operation: trajectories must be identical, not merely close."""
    reports = {}
    for vectorized in (True, False):
        world = get_scenario("paper-2022").build(scale=0.02, seed=0,
                                                 n_datasets=24)
        world.transport.vectorized = vectorized
        stats = EngineStats()
        reports[vectorized] = (run_world(world, engine="events",
                                         stats=stats), stats)
    vec, vec_stats = reports[True]
    sca, sca_stats = reports[False]
    assert vec.duration_days == pytest.approx(sca.duration_days, rel=1e-12)
    assert vec_stats.iterations == sca_stats.iterations
    assert vec.bytes_at == sca.bytes_at
    assert vec.faults_total == sca.faults_total
    assert vec.fault_histogram == sca.fault_histogram


# ----------------------------------------------------- cache == durable store
def test_table_cache_consistent_with_sqlite_after_campaign():
    """The write-through cache and the sqlite store must agree row for row
    after a full campaign (every mutation path exercised: populate, update,
    update_many, re-admission, re-routing)."""
    _, _, world = _instrumented_run(30, seed=3)
    table = world.table
    cached = {(r.dataset, r.destination): r for r in table.all()}
    stored = {(r.dataset, r.destination): r for r in table._select_db("", ())}
    assert cached.keys() == stored.keys()
    for key, rec in cached.items():
        assert rec == stored[key], key
    # derived indexes agree with ground truth
    for st in Status:
        want = sum(1 for r in stored.values() if r.status == st)
        assert table.count_status(st) == want, st
    for dst in world.spec.replicas:
        want_bytes = sum(r.bytes_transferred for r in stored.values()
                         if r.destination == dst
                         and r.status == Status.SUCCEEDED)
        assert table.bytes_at(dst) == want_bytes
        want_ds = {r.dataset for r in stored.values()
                   if r.destination == dst and r.status == Status.SUCCEEDED}
        assert set(table.succeeded_datasets(dst)) == want_ds


def test_table_update_missing_row_is_noop():
    t = TransferTable()
    t.populate(["a"], "LLNL", ["ALCF"])
    t.update("nope", "ALCF", status=Status.SUCCEEDED)   # matches no row
    assert t.get("nope", "ALCF") is None
    assert t.count_status(Status.SUCCEEDED) == 0
    assert not t.done()


def test_by_status_limit_and_source_filter():
    t = TransferTable()
    t.populate(["a", "b", "c", "d"], "LLNL", ["ALCF"])
    t.update("b", "ALCF", source="OLCF")
    rows = t.by_status(Status.NULL, destination="ALCF", source="LLNL")
    assert [r.dataset for r in rows] == ["a", "c", "d"]
    rows = t.by_status(Status.NULL, destination="ALCF", limit=2)
    assert [r.dataset for r in rows] == ["a", "b"]


# ----------------------------------------------------- streaming checksumming
def test_streaming_checksum_matches_whole_buffer():
    rng = np.random.default_rng(0)
    data = rng.bytes(3 * 4096 + 3)            # deliberately word-misaligned
    want = file_checksum(data)
    for sizes in ([len(data)], [1, 2, 3, 5, 7, len(data)], [4096] * 4,
                  [1] * 64 + [len(data)]):
        s = StreamingChecksum()
        off = 0
        for sz in sizes:
            s.update(data[off:off + sz])
            off += sz
            if off >= len(data):
                break
        s.update(data[off:])
        assert s.digest() == want
    assert StreamingChecksum().digest() == file_checksum(b"")


def test_streaming_checksum_threads_keep_their_own_scratch():
    """Eight threads hashing eight different multi-block buffers at once,
    released together and switching often, each get their own oracle
    digest: the per-thread fold scratch is never shared."""
    from repro.core.integrity import _FOLD_WORDS
    rng = np.random.default_rng(8)
    bufs = [rng.bytes(8 * _FOLD_WORDS + 4099 * i + i % 4) for i in range(8)]
    want = [file_checksum(b) for b in bufs]
    got = [None] * 8
    start = threading.Barrier(8)

    def work(i):
        s = StreamingChecksum()
        start.wait(timeout=60)
        view = memoryview(bufs[i])
        for off in range(0, len(view), 1_000_003):
            s.update(view[off:off + 1_000_003])
        got[i] = s.digest()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == want


@pytest.mark.parametrize("size", ["0", "1", "3", "scan-1", "scan+1",
                                  "3 blocks+2"])
def test_stream_file_checksum_matches_oracle(tmp_path, size):
    """``stream_file_checksum`` on files around its read buffer and the fold
    block equals the oracle, after the thread's buffer has held another
    file's bytes."""
    from repro.core.integrity import (_FOLD_WORDS, _SCAN_CHUNK,
                                      stream_file_checksum)
    n = {"0": 0, "1": 1, "3": 3, "scan-1": _SCAN_CHUNK - 1,
         "scan+1": _SCAN_CHUNK + 1, "3 blocks+2": 12 * _FOLD_WORDS + 2}[size]
    rng = np.random.default_rng(n)
    other, path = tmp_path / "other", tmp_path / "data"
    other.write_bytes(rng.bytes(_SCAN_CHUNK))
    data = rng.bytes(n)
    path.write_bytes(data)
    assert stream_file_checksum(str(other))[0] == _SCAN_CHUNK
    assert stream_file_checksum(str(path)) == (n, file_checksum(data))


def test_streaming_checksum_order_sensitive():
    a, b = b"chunk-one!", b"chunk-two?"
    h1 = StreamingChecksum().update(a).update(b).digest()
    h2 = StreamingChecksum().update(b).update(a).digest()
    assert h1 == file_checksum(a + b)
    assert h2 == file_checksum(b + a)
    assert h1 != h2
