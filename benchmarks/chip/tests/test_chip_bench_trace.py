"""The trace reduction: the union of device intervals, idle gaps named by the
host span open in them, and busy time, operation times, module runs and
compiles of a profile laid out as the TPU runtime writes it.

``record_small_trace.py`` records a small trace on a TPU for a test of the
reduction on a real profile."""
import pytest

import chip_bench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from chiplib.trace import idle_gaps, reduce_xplane, union


def test_union_merges_overlapping_intervals():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == [(0, 4), (5, 9)]


def test_idle_gaps_are_named_by_the_innermost_open_span():
    busy = [(10, 20), (50, 60)]
    spans = [(0, 100, "outer"), (25, 45, "inner")]
    gaps = idle_gaps(busy, 0, 100, spans)
    assert gaps[0] == ("outer", 40e-9)        # 60..100
    assert ("inner", 30e-9) in gaps           # 20..50, middle in "inner"
    assert ("outer", 10e-9) in gaps           # 0..10
    assert sum(g for _, g in gaps) == pytest.approx(80e-9)


class _Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


def test_planes_reduce_to_busy_ops_modules_gaps_and_compiles(monkeypatch):
    """The reduction of a profile laid out as the TPU runtime writes it: host
    annotations on ``/host:CPU``, ops and module runs on ``/device:TPU:0``;
    everything is clipped to ``bench/traced_window``."""
    import jax.profiler
    planes = [
        _Plane("/host:CPU", [("python", [
            ("bench/traced_window", 0, 1000), ("bench/step", 100, 300),
            ("bench/feed", 600, 700), ("backend_compile", 650, 660),
            ("backend_compile", 1500, 1600)])]),
        _Plane("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 50, 150), ("fusion.2", 120, 200),
                         ("fusion.1", 400, 500), ("fusion.3", 900, 1100)]),
            ("XLA Modules", [("jit_step", 50, 200), ("jit_step", 400, 500),
                             ("jit_step", 1200, 1300)])]),
    ]
    fake = type("PD", (), {"from_file": staticmethod(
        lambda path: type("D", (), {"planes": planes})())})
    monkeypatch.setattr(jax.profiler, "ProfileData", fake)
    red = reduce_xplane("unused")
    assert red.n_devices == 1 and red.compiles == 1
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(350e-9)
    assert red.op_s == pytest.approx({"fusion.1": 200e-9, "fusion.2": 80e-9,
                                      "fusion.3": 100e-9})
    assert red.modules("jit_step") == (2, pytest.approx(250e-9))
    assert red.idle_gaps == [("feed", pytest.approx(400e-9)),
                             ("step", pytest.approx(200e-9)),
                             ("no span", pytest.approx(50e-9))]
