"""Device time and device idle time attributed to the program's modules
and ``repro.*`` spans (``attribute.py``), and the readers built on it."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.hdp_bench import attribute as A
from benchmarks.hdp_bench import trace as T
from benchmarks.hdp_bench.bench import Bench

DATA = Path(__file__).parent / "data"
MS = 1e6  # ns


def planes():
    """A 100 ms window: ``jit_phi_tables`` 0-20 ms, then ``jit_z_block``
    (the kernel 30-60, a scatter 60-70, its copy 65-72). The driver
    thread's spans: ``tables.build`` 0-25, ``stage_wait`` 25-30,
    ``sweep`` 30-80 and, inside it, a collection 72-78; a worker
    thread's ``h2d`` 80-100 covers idle time that must not count."""
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0.0, 20 * MS),
           ("%hdp_z.1 = s32[8] custom-call(s32[8] %z)", 30 * MS, 30 * MS),
           ("%scatter.2 = s32[8] scatter(s32[8] %n)", 60 * MS, 10 * MS),
           ("%copy.3 = s32[8] copy(s32[8] %n)", 65 * MS, 7 * MS),
           ("%copy.4 = s32[8] copy(s32[8] %n)", 98 * MS, 5 * MS)]
    mods = [("jit_phi_tables(11)", 0.0, 20 * MS),
            ("jit_z_block(12)", 30 * MS, 42 * MS),
            ("jit_narrow_z(13)", 98 * MS, 5 * MS)]
    driver = [("hdp_bench.window", 0.0, 100 * MS),
              ("hdp_bench.iteration", 0.0, 100 * MS),
              ("repro.tables.build", 0.0, 25 * MS),
              ("repro.stage_wait", 25 * MS, 5 * MS),
              ("repro.sweep", 30 * MS, 50 * MS),
              ("repro.python.gc", 72 * MS, 6 * MS)]
    worker = [("repro.h2d", 80 * MS, 20 * MS)]
    return [("/device:TPU:0", [(T.MODULES_LINE, mods), (T.OPS_LINE, ops)]),
            ("/host:CPU", [("python", driver), ("python", worker)])]


def test_module_time_and_program_idle():
    a = A.attribute(planes())
    assert a["window_s"] == pytest.approx(0.1)
    assert a["module_s"]["jit_phi_tables"] == pytest.approx(0.020)
    assert a["module_s"]["jit_z_block"] == pytest.approx(0.042)
    # the kernel left out: the scatter and the copy, 60-72
    assert a["module_other_s"]["jit_z_block"] == pytest.approx(0.012)
    assert a["module_s"]["jit_narrow_z"] == pytest.approx(0.002)
    # idle 20-30, 72-98: under tables.build 20-25, stage_wait 25-30,
    # the collection 72-78, sweep 78-80; 80-98 under the worker's h2d
    # only, which does not count
    assert a["idle_s"] == pytest.approx(0.036)
    assert a["idle_by_span"] == {
        "tables.build": pytest.approx(0.005),
        "stage_wait": pytest.approx(0.005),
        "python.gc": pytest.approx(0.006),
        "sweep": pytest.approx(0.002)}
    assert a["idle_in_program_s"] == pytest.approx(0.018)
    assert a["program_spans"] == 4


def test_innermost_span_names_each_piece():
    spans = [(0, 10, "outer"), (2, 4, "a"), (2, 3, "b"), (6, 10, "c")]
    assert A.innermost(spans, 1, 9) == [
        (1, 2, "outer"), (2, 3, "b"), (3, 4, "a"), (4, 6, "outer"),
        (6, 9, "c")]


def test_nothing_to_read_without_window_or_device():
    no_window = [p if p[0] != "/host:CPU" else ("/host:CPU", [("python", [])])
                 for p in planes()]
    assert A.attribute(no_window) is None
    assert A.attribute([p for p in planes() if p[0] == "/host:CPU"]) is None


def _run(cell, monkeypatch, tmp_path, the_planes, counts):
    """A traced run whose trace file holds ``the_planes``."""
    f = tmp_path / f"{cell}.xplane.pb"
    f.write_bytes(b"")
    monkeypatch.setattr(A, "trace_file", lambda run: str(f))
    monkeypatch.setattr(A.T, "load", lambda path: the_planes)
    A._CACHE.clear()
    spans = {"engine.retire_wait": 0.004}
    return SimpleNamespace(cell=SimpleNamespace(name=cell, chips=1),
                           trace={"x": 1}, counts=dict(counts, window_s=0.1),
                           spans=spans)


def _readers(cell):
    return {m["name"]: read for m, read in Bench.load().cell(cell).per_layer}


def test_train_readers(monkeypatch, tmp_path, capsys):
    run = _run("train.neurips.long", monkeypatch, tmp_path, planes(),
               {"iterations": 2})
    r = _readers("train.neurips.long")
    assert r["tables_ms_per_iter.train"](run) == pytest.approx(10.0)
    assert r["delta_n_ms_per_iter.train"](run) == pytest.approx(6.0)
    assert r["idle_in_program_share.train"](run) == pytest.approx(18.0)
    log = capsys.readouterr().err
    assert "by innermost program span" in log and "'tables.build'" in log


def test_serve_readers(monkeypatch, tmp_path):
    run = _run("serve.pubmed.saturated", monkeypatch, tmp_path, planes(),
               {})
    r = _readers("serve.pubmed.saturated")
    assert r["idle_in_program_share.saturated"](run) == pytest.approx(18.0)
    assert r["retire_wait_share.saturated"](run) == pytest.approx(4.0)


def test_readers_read_nothing_from_a_program_without_names(monkeypatch,
                                                           tmp_path):
    """The parent program's trace: no ``repro.*`` span, modules named
    ``jit__phi_tables`` and ``jit_local``, no retire span."""
    old = [(n, [(ln, [(e.replace("repro.", "other.").replace(
        "jit_phi_tables", "jit__phi_tables").replace(
        "jit_z_block", "jit_local"), s, d) for e, s, d in evs])
        for ln, evs in lines]) for n, lines in planes()]
    run = _run("train.neurips.long", monkeypatch, tmp_path, old,
               {"iterations": 2})
    run.spans = {}
    r = _readers("train.neurips.long")
    for name in ("tables_ms_per_iter.train", "delta_n_ms_per_iter.train",
                 "idle_in_program_share.train"):
        assert r[name](run) is None, name
    assert _readers("serve.pubmed.poisson")["retire_wait_share.serve"](
        run) is None


def test_untraced_run_reads_nothing():
    run = SimpleNamespace(
        cell=SimpleNamespace(name="train.neurips.long", chips=1),
        trace=None, counts={"iterations": 2}, spans={})
    assert A.of(run) is None
    assert A.module_ms_per_iter(run, "jit_phi_tables") is None


def test_recorded_chip_trace_with_program_names(monkeypatch):
    """A 0.3 s window of the tiny training cell recorded on one v5e chip
    with the program's annotations and named modules (trimmed to the
    device's module and op lines and the host's ``hdp_bench.*`` and
    ``repro.*`` annotations): the readers find spans and module time."""
    path = str(DATA / "tiny_program_v5e.xplane.pb")
    a = A.attribute(T.load(path))
    assert a["program_spans"] > 0
    assert {"stage_wait", "tables.build", "sweep"} <= set(a["idle_by_span"])
    assert 0 < a["idle_in_program_s"] <= a["idle_s"] <= a["window_s"]
    assert {"jit_phi_tables", "jit_z_block", "jit_merge_stats",
            "jit_tail_l_psi"} <= set(a["module_s"])
    assert 0 < a["module_other_s"]["jit_z_block"] < a["module_s"][
        "jit_z_block"]
    monkeypatch.setattr(A, "trace_file", lambda run: path)
    A._CACHE.clear()
    run = SimpleNamespace(
        cell=SimpleNamespace(name="train.neurips.long", chips=1),
        trace={"x": 1}, counts={"iterations": 1})
    r = _readers("train.neurips.long")
    assert r["tables_ms_per_iter.train"](run) > 0
    assert r["delta_n_ms_per_iter.train"](run) > 0
    assert 0 < r["idle_in_program_share.train"](run) < 100
