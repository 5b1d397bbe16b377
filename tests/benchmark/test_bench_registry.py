"""The harness finds cells, configurations, traffic and metric readers
by name, and a new cell or metric is files, not edits."""

import json
import shutil
import subprocess
import sys

import pytest

from benchmarks.hdp_bench.bench import HERE, ROOT, Bench, BenchError


def test_committed_benchmark_resolves():
    cells = Bench.load().validate()
    names = [c.name for c in cells]
    assert names[:3] == ["train.neurips.long", "serve.pubmed.poisson",
                         "serve.pubmed.saturated"]
    for c in cells:
        e2e = {m["name"] for m in c.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert c.per_layer, c.name
        assert all(m["moves"] in e2e for m, _ in c.per_layer)


def test_throwaway_cell_and_reader_found_without_edits(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "serve.pubmed.trickle",
                              "config": "hdp-pubmed", "traffic": "trickle",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][1]["workloads"].append("serve.pubmed.trickle")
    spec["per_layer"].append({
        "name": "queue_len.trickle", "unit": "requests", "better": "lower",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_p95_ms", "workloads": ["serve.pubmed.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "serve", "rate_docs_per_s": 1, "drain_s": 60,
         "check_requests": 4, "mixture_mismatch_share_limit": 0.02}))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "queue_len.trickle.py").write_text(
        "def read(run):\n    return run.counts.get('queue')\n")
    before = {p: p.read_bytes() for p in HERE.rglob("*.json")}
    bench = Bench.load(tmp_path / "BENCHMARK.json", dirs=(tmp_path, HERE))
    cells = {c.name: c for c in bench.validate()}
    cell = cells["serve.pubmed.trickle"]
    assert cell.traffic["rate_docs_per_s"] == 1
    assert cell.config["V"] == 89987
    assert [m["name"] for m in cell.end_to_end] == ["serve_p95_ms",
                                                    "setup_s"]
    (entry, read), = cell.per_layer
    assert entry["name"] == "queue_len.trickle"

    class Run:
        counts = {"queue": 3}
    assert read(Run()) == 3
    assert {p: p.read_bytes() for p in HERE.rglob("*.json")} == before


@pytest.mark.parametrize("field,value,match", [
    ("traffic", "no_such_mix", "no traffic/no_such_mix.json"),
    ("config", "bad name", "bad configs name"),
])
def test_missing_or_bad_files_are_refused(tmp_path, field, value, match):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"][0][field] = value
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(BenchError, match=match):
        Bench.load(tmp_path / "BENCHMARK.json").validate()


def test_metric_moving_no_end_to_end_metric_is_refused(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["per_layer"][0]["moves"] = "nothing"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(BenchError, match="no end-to-end metric"):
        Bench.load(tmp_path / "BENCHMARK.json").validate()


def _run(cwd, env_extra=None):
    import os
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "benchmarks/hdp_bench/run.py", "--workload",
         "train.neurips.long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "hdp_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "program is missing" in p.stderr


def test_benchmark_json_keeps_the_contract_format():
    import re

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert len(m["name"]) <= 64
    for m in spec["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                          "device_trace")
    for m in spec["per_layer"]:
        assert len(m["layer"]) <= 200 and "bound" not in m
