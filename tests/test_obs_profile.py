"""The program on the profiler's clock: ``repro.obs`` spans as
``jax.profiler`` annotations, the ``python.gc`` span, and the stable
module names of the streaming iteration's jitted programs (CPU
profiles)."""

import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro.obs.trace import _NULL_SPAN, SpanTracer


def _profile(tmp_path, fn):
    """Run ``fn`` under a CPU ``jax.profiler`` trace; the trace's planes
    as ``ProfileData``."""
    from jax.profiler import ProfileData

    out = str(tmp_path / "prof")
    jax.profiler.start_trace(out)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    return ProfileData.from_file(path).planes


def _host_events(planes):
    return [(line.name, ev.name, ev) for p in planes
            if p.name.startswith("/host:") for line in p.lines
            for ev in line.events]


def test_span_opens_annotation_on_profiler_host_plane(tmp_path):
    tr = SpanTracer()
    tr.start()

    def work():
        with tr.span("outer.step", cat="test", block=1):
            with tr.span("inner"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)

    try:
        planes = _profile(tmp_path, work)
    finally:
        tr.stop()
    got = {name: ev for _, name, ev in _host_events(planes)}
    assert "repro.outer.step" in got and "repro.inner" in got
    outer, inner = got["repro.outer.step"], got["repro.inner"]
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
    # the JSON events stay as they were
    assert [e["name"] for e in tr.events()
            if e["ph"] == "X" and e["cat"] != "gc"] == ["inner", "outer.step"]


def test_async_spans_stay_json_only(tmp_path):
    tr = SpanTracer()
    tr.start()

    def work():
        tr.async_begin("request.queued", 1, cat="serve")
        tr.async_end("request.queued", 1, cat="serve")

    try:
        planes = _profile(tmp_path, work)
    finally:
        tr.stop()
    assert not [n for _, n, _ in _host_events(planes)
                if n.startswith("repro.")]
    assert [e["ph"] for e in tr.events() if e["ph"] in "be"] == ["b", "e"]


def test_disabled_tracer_calls_no_jax_and_takes_no_lock(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("jax called on the disabled path")

    class NoLock:
        def __enter__(self):
            raise AssertionError("lock taken on the disabled path")

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    tr = SpanTracer()
    tr._lock = NoLock()
    span = tr.span("x", cat="c", block=1)
    assert span is _NULL_SPAN
    with span:
        pass
    assert tr._gc_hook not in gc.callbacks


def test_gc_span_recorded_until_the_tracer_stops():
    tr = SpanTracer()
    tr.start()
    try:
        assert tr._gc_hook in gc.callbacks
        gc.collect()
    finally:
        tr.stop()
    assert tr._gc_hook not in gc.callbacks
    gcs = [e for e in tr.events() if e["name"] == "python.gc"]
    assert gcs and all(e["ph"] == "X" and e["cat"] == "gc" for e in gcs)
    assert {"generation": 2} in [e["args"] for e in gcs]
    n = len(tr.events())
    gc.collect()
    assert len(tr.events()) == n


def test_gc_span_on_the_profiler_host_plane(tmp_path):
    tr = SpanTracer()
    tr.start()
    try:
        planes = _profile(tmp_path, gc.collect)
    finally:
        tr.stop()
    assert "repro.python.gc" in {n for _, n, _ in _host_events(planes)}


def test_streaming_iteration_modules_have_stable_names(tmp_path, rng):
    from repro.core import hdp as H
    from repro.core.sharded import ShardedHDP
    from repro.core.streaming import StreamingHDP
    from repro.data.stream import ShardedCorpusStore
    from repro.data.synthetic import planted_topics_corpus
    from repro.launch.mesh import make_host_mesh

    corpus, _ = planted_topics_corpus(rng, D=24, V=32, K_true=3,
                                      doc_len=(8, 16))
    cfg = H.HDPConfig(K=8, V=32, bucket=8, z_impl="sparse", hist_cap=16)
    stream = StreamingHDP(ShardedHDP(make_host_mesh(), cfg),
                          ShardedCorpusStore.from_corpus(corpus, 12))
    state = stream.init_state(jax.random.key(0))

    def one_iteration():
        jax.block_until_ready(stream.iteration(state).n)

    planes = _profile(tmp_path, one_iteration)
    modules = {dict(ev.stats).get("hlo_module")
               for _, _, ev in _host_events(planes)}
    assert {"jit_phi_tables", "jit_z_block", "jit_merge_stats",
            "jit_split_keys", "jit_tail_l_psi"} <= modules
    assert not {m for m in modules if m and "lambda" in m}


@pytest.mark.parametrize("scope,module", [("ppu_draw", "jit_phi_tables"),
                                          ("tables", "jit_phi_tables"),
                                          ("delta_n", "jit_z_block"),
                                          ("d_histogram", "jit_z_block")])
def test_named_scopes_reach_the_hlo(rng, scope, module):
    """The named scopes inside the fused programs are op metadata of the
    lowered module (they change no operation)."""
    from repro.core import hdp as H
    from repro.core.sharded import ShardedHDP
    from repro.launch.mesh import make_host_mesh

    cfg = H.HDPConfig(K=8, V=32, bucket=8, z_impl="sparse", hist_cap=16)
    sh = ShardedHDP(make_host_mesh(), cfg)
    n = np.zeros((8, 32), np.int32)
    psi = np.full((8,), 1 / 8, np.float32)
    key = jax.random.key(0)
    if module == "jit_phi_tables":
        low = jax.jit(sh.phi_tables_fn()).lower(n, psi, key)
    else:
        _, _, tabs = jax.jit(sh.phi_tables_fn())(n, psi, key)
        z = np.zeros((4, 8), np.int32)
        toks = np.ones((4, 8), np.int32)
        mask = np.ones((4, 8), bool)
        low = jax.jit(sh.z_block_fn()).lower(tabs, z, toks, mask, psi, key)
    text = low.as_text(debug_info=True)
    assert low.as_text().startswith(f"module @{module}")
    assert f"/{scope}/" in text
