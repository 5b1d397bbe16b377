"""One run of one benchmark cell: set-up, a measured window, the check
against the plain reference, and the result line.

  python3 benchmarks/hdp_bench/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a ``jax.profiler`` trace
of the window and from the program's spans and counters. The last line
of standard output is the result; standard error ends with every number
compared for ``correct``, each beside its limit.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from benchmarks.hdp_bench import peaks as P
from benchmarks.hdp_bench import trace as T
from benchmarks.hdp_bench.bench import ROOT, Bench, BenchError

OUT = ROOT / ".bench_out"


@dataclass
class Outcome:
    """What a cell driver hands back."""
    e2e: dict                      # end-to-end metric name -> value
    checks: list                   # (name, value, limit): value <= limit
    attempted: int
    failed: int
    memory_peak_bytes: int
    counts: dict = field(default_factory=dict)
    work: dict = field(default_factory=dict)
    spans: dict = field(default_factory=dict)   # span name -> seconds
    span_args: list = field(default_factory=list)
    trace: dict | None = None      # trace.reduce() of the window


@dataclass
class RunData:
    """What a per-layer metric reader sees."""
    cell: object
    peaks: dict
    outcome: Outcome

    @property
    def counts(self):
        return self.outcome.counts

    @property
    def work(self):
        return self.outcome.work

    @property
    def spans(self):
        return self.outcome.spans

    @property
    def trace(self):
        return self.outcome.trace


def note(name: str):
    """A harness annotation on the profiler's clock (cheap when off)."""
    import jax

    return jax.profiler.TraceAnnotation(T.HARNESS_PREFIX + name)


class Window:
    """The measured window. With a trace directory it records a
    ``jax.profiler`` trace and the program's spans (``repro.obs``) over
    exactly the window, and counts compilations inside it either way."""

    def __init__(self, trace_dir=None):
        self.trace_dir = trace_dir
        self.compiles = 0
        self._open = False

    def _on_event(self, event, duration, **_):
        if self._open and event.endswith("backend_compile_duration"):
            self.compiles += 1

    def __enter__(self):
        import jax
        from repro import obs

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            obs.enable_tracing()
            jax.profiler.start_trace(str(self.trace_dir))
        self._note = note("window")
        self._note.__enter__()
        self._open = True
        self.t0 = time.perf_counter()
        return self

    def close(self):
        """End the window and the tracing (idempotent); returns the
        window's length in seconds."""
        if self._open:
            self.t1 = time.perf_counter()
            self._open = False
            self._note.__exit__(None, None, None)
            if self.trace_dir:
                import jax
                from repro import obs

                obs.tracer().stop()
                jax.profiler.stop_trace()
        return self.t1 - self.t0

    def __exit__(self, *exc):
        self.close()
        return False

    def spans(self) -> tuple:
        """(seconds per program span name, args of every span) recorded
        in the window."""
        from repro import obs

        tot, args = {}, []
        for ev in obs.tracer().events():
            if ev.get("ph") == "X":
                tot[ev["name"]] = tot.get(ev["name"], 0.0) + ev["dur"] * 1e-6
                args.append((ev["name"], ev.get("args", {})))
        return tot, args

    def reduce(self, kernels=("hdp_z",)):
        return T.reduce(T.load(T.find_xplane(str(self.trace_dir))), kernels)


class Phases:
    """Wall seconds of the named steps of a set-up, for the log."""

    def __init__(self):
        self.t = time.perf_counter()
        self.done = []

    def __call__(self, name: str):
        now = time.perf_counter()
        self.done.append((name, now - self.t))
        self.t = now

    def __str__(self):
        return ", ".join(f"{n} {s:.2f} s" for n, s in self.done)


class CompileLog:
    """Programs compiled or loaded from the persistent cache in this
    process, for the log: set-up time that a warm cache should remove."""

    def __init__(self):
        import jax

        self.programs, self.backend_s, self.trace_s = 0, 0.0, 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event.endswith("backend_compile_duration"):
            self.programs += 1
            self.backend_s += secs
        elif event.endswith(("jaxpr_trace_duration",
                             "jaxpr_to_mlir_module_duration")):
            self.trace_s += secs

    def __str__(self):
        return (f"{self.programs} programs compiled or loaded in "
                f"{self.backend_s:.2f} s (persistent cache: {self.hits} hits, "
                f"{self.misses} misses), tracing and lowering "
                f"{self.trace_s:.2f} s")


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def configure_jax():
    """Persistent compilation cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), every program cached."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t0: float,
             peaks: dict, devices, log=sys.stderr) -> dict:
    """Drive one run of ``cell`` on ``devices``; returns the result."""
    from benchmarks.hdp_bench import serve_cell, train_cell

    driver = {"train": train_cell, "serve": serve_cell}[cell.traffic["kind"]]
    trace_dir = OUT / "trace" / cell.name if trace else None
    compiles = CompileLog()
    out = driver.run(cell, seed=seed, seconds=seconds, t0=t0,
                     trace_dir=trace_dir, log=log)
    print(f"hdp_bench: {compiles}", file=log, flush=True)
    if trace:
        data = RunData(cell=cell, peaks=peaks, outcome=out)
        metrics = {}
        for entry, read in cell.per_layer:
            v = read(data)
            if v is not None:
                metrics[entry["name"]] = {"value": float(v),
                                          "unit": entry["unit"]}
    else:
        metrics = {m["name"]: {"value": float(out.e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes":
              out.memory_peak_bytes}
    if trace and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
    correct = out.failed == 0 and all(v <= lim for _, v, lim in out.checks)
    res = {"correct": bool(correct), "attempted": int(out.attempted),
           "failed": int(out.failed), "metrics": metrics, "device": device}
    if trace and out.trace is not None:
        res["breakdown"] = {"device_ops": out.trace["device_ops"],
                            "idle_gaps": out.trace["idle_gaps"]}
    res["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in out.checks}
    return res


def print_result(res: dict, log=sys.stderr):
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=log)
    log.flush()
    print(json.dumps(res), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0=None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    try:
        cell = Bench.load().cell(args.workload)
    except BenchError as e:
        print(f"hdp_bench: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"hdp_bench: the program is missing: {e}", file=sys.stderr)
        return 2
    import jax

    cache = configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"hdp_bench: no TPU (JAX's first device is "
              f"{devices[0].platform!r}); the benchmark runs only on the "
              "chip", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"hdp_bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    try:
        peaks = P.for_kind(devices[0].device_kind)
    except KeyError as e:
        print(f"hdp_bench: {e}", file=sys.stderr)
        return 3
    print(f"hdp_bench: {cell.name} seed {args.seed} on "
          f"{devices[0].device_kind} x{cell.chips}, compile cache {cache}",
          file=sys.stderr, flush=True)
    res = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t0=t0, peaks=peaks,
                   devices=devices[:cell.chips])
    print_result(res)
    return 0
