"""The per-layer readers on cells of one and of four chips.

Least time is shared over the cell's chips, so an MFU reads the same
work done four times as fast on four chips as it reads on one; kernel and
module time are chip-seconds summed over the device planes, so a
roofline share reads the same summed kernel time alike; busy and idle
time are means over the planes; and a trace with fewer device planes than
the cell has chips reads nothing on the device.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.hdp_bench import attribute as A
from benchmarks.hdp_bench import peaks as P
from benchmarks.hdp_bench import trace as T
from benchmarks.hdp_bench.bench import Bench

DATA = Path(__file__).parent / "data"
MS = 1e6  # ns
V5E = P.for_kind("TPU v5 lite")
WORK = {"hdp_z": {"flops": 1.0e9, "bytes": 3.0e8},
        "iteration": {"flops": 2.0e9, "bytes": 5.0e8},
        "requests": [{"flops": 1.0e6, "bytes": 2.0e6}] * 3}
COUNTS = {"iterations": 2, "window_s": 0.5, "step_s_in_window": 0.25,
          "steps_in_window": 10, "completed_in_window": 12, "burnin": 3,
          "slots": 4}
SPANS = {"stage_wait": 0.01, "tables.build": 0.02,
         "engine.retire_wait": 0.03}


def readers(cell):
    return {m["name"]: read for m, read in Bench.load().cell(cell).per_layer}


def a_run(cell, chips, trace=None, counts=COUNTS):
    return SimpleNamespace(cell=SimpleNamespace(name=cell, chips=chips),
                           peaks=V5E, trace=trace, work=WORK,
                           counts=dict(counts), spans=dict(SPANS))


def test_mfu_reads_the_same_work_alike_on_four_chips():
    r_train = readers("train.neurips.long")["train_mfu"]
    r_serve = readers("serve.pubmed.poisson")["serve_mfu"]
    one = a_run("train.neurips.long", 1)
    quarter = {k: COUNTS[k] / 4 for k in ("window_s", "step_s_in_window")}
    four = a_run("train.neurips.long", 4, counts=dict(COUNTS, **quarter))
    assert r_train(four) == pytest.approx(r_train(one), rel=1e-12)
    assert r_serve(four) == pytest.approx(r_serve(one), rel=1e-12)
    # the same wall time on four chips is a quarter of the utilization
    slow = a_run("train.neurips.long", 4)
    assert r_train(slow) == pytest.approx(r_train(one) / 4, rel=1e-12)


def _reduced(devices, kernel_s):
    return {"window_s": 1.0, "busy_s": 0.5, "devices": devices,
            "kernel_s": {"hdp_z": kernel_s},
            "kernel_events": {"hdp_z": 8}}


def test_roofline_reads_the_same_summed_kernel_time_alike():
    r = readers("train.neurips.long")
    one = a_run("train.neurips.long", 1, _reduced(1, 0.4))
    four = a_run("train.neurips.long", 4, _reduced(4, 0.4))
    for name in ("hdp_z_roofline.train", "hdp_z_ms_per_iter.train",
                 "device_idle_share.train"):
        assert r[name](four) == r[name](one), name
    assert r["hdp_z_ms_per_iter.train"](four) == pytest.approx(200.0)


def four_planes():
    """Four device planes under one 100 ms window: each runs the kernel
    (10 ms on device n, at 10-20 ms) and a fusion (0-5 ms); device 0
    also runs the tables module (50-90 ms). The driver's ``stage_wait``
    span covers 20-50 ms."""
    out = []
    for n in range(4):
        ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0.0, 5 * MS),
               ("%hdp_z.1 = s32[8] custom-call(s32[8] %z)", 10 * MS,
                10 * MS)]
        mods = [("jit_z_lane(1)", 0.0, 20 * MS)]
        if n == 0:
            ops.append(("%fusion.2 = f32[8] fusion(f32[8] %q)", 50 * MS,
                        40 * MS))
            mods.append(("jit_phi_tables(2)", 50 * MS, 40 * MS))
        out.append((f"/device:TPU:{n}",
                    [(T.MODULES_LINE, mods), (T.OPS_LINE, ops)]))
    driver = [("hdp_bench.window", 0.0, 100 * MS),
              ("repro.stage_wait", 20 * MS, 30 * MS)]
    return out + [("/host:CPU", [("python", driver)])]


def test_reduce_means_busy_and_sums_kernel_time_over_four_planes():
    r = T.reduce(four_planes())
    assert r["devices"] == 4
    # busy: 15 ms on three devices, 55 ms on device 0
    assert r["busy_s"] == pytest.approx((3 * 0.015 + 0.055) / 4)
    assert r["kernel_s"]["hdp_z"] == pytest.approx(4 * 0.010)
    assert r["kernel_events"]["hdp_z"] == 4
    a = A.attribute(four_planes())
    assert a["devices"] == 4
    assert a["module_s"]["jit_z_lane"] == pytest.approx(4 * 0.015)
    assert a["module_s"]["jit_phi_tables"] == pytest.approx(0.040)
    # idle: 85 ms on three devices, 45 ms on device 0
    assert a["idle_s"] == pytest.approx((3 * 0.085 + 0.045) / 4)
    # stage_wait, 20-50 ms, is idle on every device: 30 ms each
    assert a["idle_by_span"] == {"stage_wait": pytest.approx(0.030)}


def _with_trace(monkeypatch, tmp_path, planes):
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(b"")
    monkeypatch.setattr(A, "trace_file", lambda run: str(f))
    monkeypatch.setattr(A.T, "load", lambda path: planes)
    A._CACHE.clear()


@pytest.mark.parametrize("planes_kept", [1, 3])
def test_fewer_planes_than_chips_read_nothing_on_the_device(
        monkeypatch, tmp_path, planes_kept):
    planes = four_planes()
    kept = planes[:planes_kept] + planes[-1:]
    _with_trace(monkeypatch, tmp_path, kept)
    r = readers("train.neurips.long")
    short = a_run("train.neurips.long", 4, T.reduce(kept))
    for name in ("hdp_z_roofline.train", "hdp_z_ms_per_iter.train",
                 "device_idle_share.train", "tables_ms_per_iter.train",
                 "delta_n_ms_per_iter.train",
                 "idle_in_program_share.train"):
        assert r[name](short) is None, name
    # host-clock and span readers do not depend on the trace
    assert r["train_mfu"](short) is not None
    assert r["stage_wait_share.train"](short) == pytest.approx(2.0)
    # the same planes read on a cell of as many chips as they hold
    whole = a_run("train.neurips.long", planes_kept, T.reduce(kept))
    assert r["hdp_z_ms_per_iter.train"](whole) == pytest.approx(
        1e3 * 0.010 * planes_kept / 2)
    assert r["idle_in_program_share.train"](whole) is not None


# every per-layer reader of the three kinds of cell, on the two traces
# recorded on one v5e chip with the work, counts and spans of ``a_run``:
# the numbers the readers gave when they knew only one chip
ONE_CHIP = {
    ("tiny_v5e.xplane.pb", "train.neurips.long"): {
        "train_mfu": 0.2442002442002442,
        "hdp_z_roofline.train": 5.884291244018988,
        "hdp_z_ms_per_iter.train": 3.1125275000000006,
        "device_idle_share.train": 93.39441776892163,
        "stage_wait_share.train": 2.0, "tables_wait_share.train": 4.0,
        "tables_ms_per_iter.train": None,
        "delta_n_ms_per_iter.train": None,
        "idle_in_program_share.train": None},
    ("tiny_v5e.xplane.pb", "serve.pubmed.poisson"): {
        "serve_mfu": 0.0029304029304029304,
        "hdp_z_roofline.serve": 5.884291244018988,
        "device_idle_share.serve": 93.39441776892163,
        "slot_occupancy.serve": 90.0,
        "idle_in_program_share.serve": None,
        "retire_wait_share.serve": 6.0},
    ("tiny_v5e.xplane.pb", "serve.pubmed.saturated"): {
        "slot_occupancy.saturated": 90.0,
        "device_idle_share.saturated": 93.39441776892163,
        "idle_in_program_share.saturated": None,
        "retire_wait_share.saturated": 6.0},
    ("tiny_program_v5e.xplane.pb", "train.neurips.long"): {
        "train_mfu": 0.2442002442002442,
        "hdp_z_roofline.train": 0.7304160873002611,
        "hdp_z_ms_per_iter.train": 25.074773999999998,
        "device_idle_share.train": 83.21972637137736,
        "stage_wait_share.train": 2.0, "tables_wait_share.train": 4.0,
        "tables_ms_per_iter.train": 1.1271270000000002,
        "delta_n_ms_per_iter.train": 1.7591560000000002,
        "idle_in_program_share.train": 36.64310715919572},
    ("tiny_program_v5e.xplane.pb", "serve.pubmed.poisson"): {
        "serve_mfu": 0.0029304029304029304,
        "hdp_z_roofline.serve": 0.7304160873002611,
        "device_idle_share.serve": 83.21972637137736,
        "slot_occupancy.serve": 90.0,
        "idle_in_program_share.serve": 36.64310715919572,
        "retire_wait_share.serve": 6.0},
    ("tiny_program_v5e.xplane.pb", "serve.pubmed.saturated"): {
        "slot_occupancy.saturated": 90.0,
        "device_idle_share.saturated": 83.21972637137736,
        "idle_in_program_share.saturated": 36.64310715919572,
        "retire_wait_share.saturated": 6.0},
}


@pytest.mark.parametrize("trace_file,cell", sorted(ONE_CHIP))
def test_one_chip_readings_are_unchanged(monkeypatch, trace_file, cell):
    path = str(DATA / trace_file)
    monkeypatch.setattr(A, "trace_file", lambda run: path)
    A._CACHE.clear()
    run = a_run(cell, 1, T.reduce(T.load(path)))
    got = {name: read(run) for name, read in readers(cell).items()}
    assert got == ONE_CHIP[(trace_file, cell)]
