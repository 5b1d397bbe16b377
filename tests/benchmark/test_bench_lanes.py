"""``correct`` for a training cell on four chips, on the CPU: the cell
``tiny.train.lanes4`` sweeps four lanes on four virtual CPU devices.

A sound run is correct, every check reads 0, and iteration 1 leaves z, n
and the chain key exactly as the one-chip cell ``tiny.train`` does at the
same seed. Two faults of the exchange between chips make it not correct:
one lane's delta left out of the merge (``n_recount_mismatch``), and all
four lanes on one device (``lanes_missing``).

Each case runs in a subprocess with
``--xla_force_host_platform_device_count=4``, so the rest of the suite
keeps one CPU device.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

_BODY = """
    import json, sys, time
    import jax
    from benchmarks.hdp_bench import harness, peaks, train_cell
    from benchmarks.hdp_bench.bench import HERE, Bench

    DATA = HERE.parents[1] / "tests" / "benchmark" / "data"
    FAULT = sys.argv[1]

    if FAULT == "dropped_delta":
        from repro.data import deltawire

        merge = deltawire.reduce_packed
        deltawire.reduce_packed = lambda packs, shape=None: merge(
            packs[:-1], shape=shape)
    elif FAULT == "one_device":
        from repro.core.streaming import StreamingHDP

        init = StreamingHDP.__init__

        def stacked(self, *a, **kw):
            init(self, *a, **kw)
            self._lane_devices = self._lane_devices[:1] * self.n_devices
        StreamingHDP.__init__ = stacked

    taken = []
    real_taken = train_cell._taken

    def spy(state, z=None):
        out = real_taken(state, z)
        taken.append(out)
        return out
    train_cell._taken = spy

    def run(name):
        taken.clear()
        cell = Bench.load(DATA / "BENCHMARK.json",
                          dirs=(DATA, HERE)).cell(name)
        res = harness.run_cell(
            cell, seed=3, seconds=0.3, trace=False, t0=time.perf_counter(),
            peaks=peaks.for_kind("TPU v5 lite"),
            devices=jax.devices()[:cell.chips])
        it1 = taken[1]   # the state after iteration 1
        return res, {"z": it1["z"].tolist(),
                     "n": jax.device_get(it1["n"]).tolist(),
                     "key": jax.random.key_data(it1["key"]).tolist()}

    lanes, lanes_it1 = run("tiny.train.lanes4")
    out = {"lanes": lanes}
    if FAULT == "none":
        one, one_it1 = run("tiny.train")
        out["one"] = one
        out["same_it1"] = {k: lanes_it1[k] == one_it1[k] for k in one_it1}
    print(json.dumps(out))
"""


def run_case(fault: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_BODY), fault],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stdout[-4000:] + "\n" + p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_lanes_are_correct_and_sweep_the_one_chip_chain():
    out = run_case("none")
    lanes, one = out["lanes"], out["one"]
    assert lanes["correct"], lanes["checks"]
    assert lanes["device"]["count"] == 4 and one["device"]["count"] == 1
    assert all(c["value"] == 0 for c in lanes["checks"].values())
    assert set(lanes["checks"]) == set(one["checks"])
    assert "lanes_missing" in lanes["checks"]
    assert out["same_it1"] == {"z": True, "n": True, "key": True}


@pytest.mark.parametrize("fault,caught_by", [
    ("dropped_delta", "n_recount_mismatch.it1"),
    ("one_device", "lanes_missing"),
])
def test_broken_exchange_between_chips_is_not_correct(fault, caught_by):
    res = run_case(fault)["lanes"]
    assert not res["correct"], res["checks"]
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"], res["checks"]
