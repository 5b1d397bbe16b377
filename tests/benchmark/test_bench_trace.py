"""The reduction from a profiler trace to busy, idle and kernel time."""

from pathlib import Path

import pytest

from benchmarks.hdp_bench import trace as T

DATA = Path(__file__).parent / "data"
MS = 1e6  # ns


def planes(device_events, notes):
    """A trace as ``trace.load`` returns it: one device plane with an
    ``XLA Ops`` line, one host plane with the harness annotations."""
    return [("/device:TPU:0", [("XLA Modules", [("jit_step", 0.0, 99 * MS)]),
                               (T.OPS_LINE, device_events)]),
            ("/host:CPU", [("python", notes)])]


def test_busy_idle_and_kernel_time():
    ops = [("hdp_z", 10 * MS, 20 * MS),          # 10-30
           ("fusion.1", 25 * MS, 10 * MS),       # 25-35 overlaps
           ("hdp_z", 60 * MS, 10 * MS),          # 60-70
           ("copy", 95 * MS, 20 * MS)]           # 95-115, clipped at 100
    notes = [("hdp_bench.window", 0.0, 100 * MS),
             ("hdp_bench.iteration", 5 * MS, 40 * MS),   # 5-45
             ("hdp_bench.wait", 45 * MS, 15 * MS)]       # 45-60
    r = T.reduce(planes(ops, notes))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.025 + 0.010 + 0.005)
    assert r["kernel_s"]["hdp_z"] == pytest.approx(0.030)
    assert r["kernel_events"]["hdp_z"] == 2
    assert r["device_ops"][0] == ["jit_step:hdp_z", pytest.approx(0.030)]
    # gaps 35-60 (its middle under "wait"), 70-95 (under nothing) and
    # 0-10 (its middle, 5 ms, is where "iteration" starts), longest first
    assert r["idle_gaps"] == [["wait", pytest.approx(0.025)],
                              ["outside harness calls", pytest.approx(0.025)],
                              ["iteration", pytest.approx(0.010)]]


def test_gap_named_by_innermost_annotation():
    ops = [("a", 0.0, 10 * MS), ("b", 30 * MS, 10 * MS)]
    notes = [("hdp_bench.window", 0.0, 40 * MS),
             ("hdp_bench.iteration", 0.0, 40 * MS),
             ("hdp_bench.submit", 15 * MS, 10 * MS)]
    r = T.reduce(planes(ops, notes))
    assert r["idle_gaps"] == [["submit", pytest.approx(0.020)]]


def test_no_device_events_reads_nothing():
    assert T.reduce([("/host:CPU", [("python", [])])]) is None


def test_window_defaults_to_device_span():
    ops = [("x", 10 * MS, 10 * MS), ("y", 30 * MS, 10 * MS)]
    r = T.reduce(planes(ops, []))
    assert r["window_s"] == pytest.approx(0.030)
    assert r["busy_s"] == pytest.approx(0.020)


def test_recorded_chip_trace():
    """A trace recorded on one v5e chip (a 1 s window of the tiny
    training cell, compiled kernel): the reduction finds the device,
    the harness window and the kernel's events."""
    planes = T.load(str(DATA / "tiny_v5e.xplane.pb"))
    assert any(T.is_device_plane(name) for name, _ in planes)
    r = T.reduce(planes)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["kernel_events"]["hdp_z"] > 0
    assert 0 < r["kernel_s"]["hdp_z"] <= r["busy_s"]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(t > 0 for _, t in r["idle_gaps"])


@pytest.mark.parametrize("op,kernel", [
    ("%hdp_z.1 = (s32[16,32]) custom-call(s32[16,32] %copy-done.5)", True),
    ("%hdp_z = s32[8] custom-call(s32[8] %x)", True),
    ("%fusion.3 = s32[8] fusion(s32[8] %hdp_z.1)", False),
    ("%hdp_zeta.2 = s32[8] fusion(s32[8] %x)", False),
])
def test_kernel_events_by_their_own_name(op, kernel):
    assert T.is_kernel(T.op_name(op), "hdp_z") is kernel
