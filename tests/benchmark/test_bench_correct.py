"""``correct`` on the CPU at a small size: a sound run passes, the
control (the program's compact bfloat16/int16 tables) fails, and so does
a run whose timed path is broken underneath.

The cell has one chip, so the fault of an exchange between chips left
out cannot occur in it.
"""

import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks.hdp_bench import control, harness, peaks
from benchmarks.hdp_bench.bench import HERE, Bench

DATA = Path(__file__).parent / "data"


@pytest.fixture
def fresh_traces():
    """A broken function patched in must be traced anew, and must not
    stay in JAX's caches for the next test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def run(name, seed=3):
    # serving needs a few dozen requests for its sample; training only
    # its first iterations
    seconds = 2.0 if name.endswith("serve") else 0.3
    cell = Bench.load(DATA / "BENCHMARK.json", dirs=(DATA, HERE)).cell(name)
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            t0=time.perf_counter(),
                            peaks=peaks.for_kind("TPU v5 lite"),
                            devices=jax.devices()[:1])


@pytest.mark.parametrize("name", ["tiny.train", "tiny.serve"])
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("name", ["tiny.train", "tiny.serve"])
def test_control_is_not_correct(name):
    with control.compact():
        res = run(name)
    assert not res["correct"], res["checks"]


def _kernel_fault(monkeypatch, module, fault):
    real = module.hdp_z_pallas

    def broken(tokens, mask, z, *a, **kw):
        z_new, m = real(tokens, mask, z, *a, **kw)
        if fault == "unchanged":
            return z, jnp.zeros_like(m)
        if fault == "half_batch":   # every other row left unswept
            rows = jnp.arange(z.shape[0])[:, None]
            return jnp.where(rows % 2 == 0, z, z_new), m
        # the first token of row 0 moved to the next topic, as the
        # kernel would have written it: z and the row's histogram agree
        k = kw["kk"]
        old = z_new[0, 0]
        new = (old + 1) % k
        m = m.at[0, old].add(-1).at[0, new].add(1)
        return z_new.at[0, 0].set(new), m

    monkeypatch.setattr(module, "hdp_z_pallas", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "token"])
def test_broken_training_step_is_not_correct(monkeypatch, fresh_traces,
                                             fault):
    from repro.kernels.hdp_z import ops

    _kernel_fault(monkeypatch, ops, fault)
    res = run("tiny.train")
    assert not res["correct"], res["checks"]
    assert res["checks"]["z_mismatch_share.it1"]["value"] > 0


def test_iteration_returning_its_state_is_not_correct(monkeypatch):
    from repro.core.streaming import StreamingHDP

    monkeypatch.setattr(StreamingHDP, "iteration",
                        lambda self, state, **kw: state)
    res = run("tiny.train")
    assert not res["correct"], res["checks"]


def _after_first_call(monkeypatch, break_it):
    """Iteration 1 (set-up) runs sound; ``break_it(stream, state)`` then
    breaks every later call, the window's and the check's."""
    from repro.core.streaming import StreamingHDP

    real = StreamingHDP.iteration
    calls = []

    def iteration(self, state, **kw):
        calls.append(1)
        out = real(self, state, **kw)
        if len(calls) == 1:
            break_it(self, out)
        return out

    monkeypatch.setattr(StreamingHDP, "iteration", iteration)


def _returns_state(stream, state):
    stream.iteration = lambda s, **kw: s


def _stale_tables(stream, state):
    phi_fn, first = stream._phi_fn, []

    def tables(*a):
        if not first:
            first.append(phi_fn(*a))
        return first[0]
    stream._phi_fn = tables


def _frozen_psi(stream, state):
    tail = stream._tail_fn
    stream._tail_fn = lambda dh, psi, k_l, k_psi: (
        tail(dh, psi, k_l, k_psi)[0], psi)


def _half_rows_unswept(stream, state):
    """Every other row of each block keeps its topics; n and the
    histogram follow the rows as returned, so n agrees with z."""
    from repro.core import hdp as H

    z_fn, cfg = stream._z_fn, stream.cfg

    def sweep(tables, z, tokens, mask, psi, key):
        z_old = z.copy()
        z_new, _, dh = z_fn(tables, z, tokens, mask, psi, key)
        rows = jnp.arange(z_old.shape[0])[:, None] % 2 == 0
        z_new = jnp.where(rows, z_old, z_new)
        return z_new, H.delta_n(z_old, z_new, tokens, mask, cfg.K,
                                cfg.V), dh
    stream._z_fn = sweep


@pytest.mark.parametrize("fault,caught_by", [
    (_returns_state, "key_mismatch"),
    (_stale_tables, "varphi_mismatch.chk"),
    (_frozen_psi, "psi_max_abs_diff.chk"),
    (_half_rows_unswept, "z_mismatch_share.chk"),
])
def test_window_iteration_broken_after_the_first_is_not_correct(
        monkeypatch, fault, caught_by):
    _after_first_call(monkeypatch, fault)
    res = run("tiny.train")
    assert not res["correct"], res["checks"]
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"], res["checks"]
    assert res["checks"]["varphi_mismatch.it1"]["value"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "token"])
def test_broken_foldin_step_is_not_correct(monkeypatch, fresh_traces,
                                           fault):
    from repro.core import conformance

    _kernel_fault(monkeypatch, conformance, fault)
    res = run("tiny.serve")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["altered", "dropped"])
def test_broken_answers_are_not_correct(monkeypatch, fault):
    from repro.serve.engine import ServeEngine

    real = ServeEngine.drain_completed

    def broken(self):
        out = real(self)
        if out:
            rid = min(out)
            if fault == "dropped" and rid == 0:
                del out[rid]
            elif fault == "altered":
                out[rid] = out[rid].copy()
                out[rid][0] += 1e-3
        return out

    monkeypatch.setattr(ServeEngine, "drain_completed", broken)
    res = run("tiny.serve")
    assert not res["correct"], res["checks"]
