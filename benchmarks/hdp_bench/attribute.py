"""Device time and device idle time of a traced window, attributed to the
program's own names: its jitted modules (``jit_<name>``) and the
``repro.<span>`` annotations that ``repro.obs`` opens on the profiler's
clock while it records.

It reads the planes of the run's trace, the file ``Window.reduce`` reads
(``trace.load``), once per run: the readers of ``metrics/`` share them.

  module time    the union of a module's operation intervals inside the
                 window, summed over the device planes (an operation
                 belongs to the module event it starts in, as in
                 ``trace.device_ops``); ``other`` leaves out the
                 kernel's own operations
  program idle   the stretches of the window with no operation on a
                 device, cut by the ``repro.*`` spans of the host thread
                 that holds the harness's ``hdp_bench.window``: each
                 piece goes to the innermost span over it, and pieces
                 under no such span to none. Spans of other threads
                 (the streaming pipeline's stagers, write-back) do not
                 count. Averaged over the device planes, like busy time.
"""

from __future__ import annotations

import os

from benchmarks.hdp_bench import trace as T

PROGRAM_PREFIX = "repro."


def window_thread(planes: list):
    """(start, end, events of its host line) of the harness window, or
    None where the trace has no window."""
    for name, lines in planes:
        if T.is_device_plane(name):
            continue
        for _, evs in lines:
            for n, s, d in evs:
                if n == T.WINDOW:
                    return s, s + d, evs
    return None


def innermost(spans: list, lo: float, hi: float) -> list:
    """[(start, end, name)] pieces of [lo, hi] under at least one of the
    nested ``spans`` [(start, end, name)], each named by the innermost
    span over it."""
    events = []
    for i, (s, e, _) in enumerate(spans):
        events.append((s, 1, s - e, i))   # at one time: ends, then the
        events.append((e, 0, 0.0, i))     # longer of two starts first
    events.sort()
    out, open_, t_prev = [], [], lo
    for t, starts, _, i in events:
        a, b = max(t_prev, lo), min(t, hi)
        if open_ and b > a:
            out.append((a, b, spans[open_[-1]][2]))
        if starts:
            open_.append(i)
        elif open_ and open_[-1] == i:
            open_.pop()
        elif i in open_:
            open_.remove(i)
        t_prev = t
    return out


def _overlap(xs: list, ys: list):
    """Yield (start, end, y) for the overlaps of two sorted lists of
    disjoint intervals, ``xs`` [(s, e)] and ``ys`` [(s, e, y)]."""
    i = j = 0
    while i < len(xs) and j < len(ys):
        s = max(xs[i][0], ys[j][0])
        e = min(xs[i][1], ys[j][1])
        if e > s:
            yield s, e, ys[j][2]
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1


def attribute(planes: list, kernel: str = "hdp_z"):
    """Module and program-idle seconds of the traced window; None where
    the trace has no window or no device operation.

    Returns ``window_s``; ``module_s`` and ``module_other_s`` (module
    name -> seconds, summed over devices); ``program_spans``, the count
    of the window thread's ``repro.*`` spans inside the window; and,
    averaged over devices, ``idle_s``, ``idle_in_program_s`` and
    ``idle_by_span`` (innermost span name without the prefix -> idle
    seconds).
    """
    win = window_thread(planes)
    devs = T.device_ops(planes)
    if win is None or not devs:
        return None
    lo, hi, thread = win
    spans = [(s, s + d, n[len(PROGRAM_PREFIX):]) for n, s, d in thread
             if n.startswith(PROGRAM_PREFIX) and s < hi and s + d > lo]
    pieces = innermost(spans, lo, hi)
    module_s, other_s, idle_by, idle_s = {}, {}, {}, 0.0
    for evs in devs:
        by_mod: dict = {}
        busy = []
        for name, s, d in evs:
            mod, _, op = name.rpartition(":")
            iv = (max(s, lo), min(s + d, hi))
            if iv[1] <= iv[0]:
                continue
            busy.append(iv)
            whole, rest = by_mod.setdefault(mod, ([], []))
            whole.append(iv)
            if not T.is_kernel(op, kernel):
                rest.append(iv)
        for mod, (whole, rest) in by_mod.items():
            module_s[mod] = module_s.get(mod, 0.0) + _length(whole)
            other_s[mod] = other_s.get(mod, 0.0) + _length(rest)
        idle, edge = [], lo  # the gaps, as ``trace.reduce`` finds them
        for s, e in T._union(busy) + [[hi, hi]]:
            if s > edge:
                idle.append((edge, s))
            edge = max(edge, e)
        idle_s += sum(e - s for s, e in idle) * 1e-9
        for s, e, name in _overlap(idle, pieces):
            idle_by[name] = idle_by.get(name, 0.0) + (e - s) * 1e-9
    n = len(devs)
    idle_by = {k: v / n for k, v in idle_by.items()}
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle_s / n,
            "module_s": module_s, "module_other_s": other_s,
            "program_spans": len(spans),
            "idle_in_program_s": sum(idle_by.values()),
            "idle_by_span": idle_by}


def _length(intervals: list) -> float:
    return sum(e - s for s, e in T._union(intervals)) * 1e-9


_CACHE: dict = {}


def trace_file(run):
    """The profiler trace of this run's window, or None where it was not
    traced. The directory is the one ``harness.run_cell`` gives the
    profiler; this lookup should go once ``Outcome`` keeps the planes
    that ``Window.reduce`` loads."""
    from benchmarks.hdp_bench.harness import OUT

    if not run.trace:
        return None
    try:
        return T.find_xplane(str(OUT / "trace" / run.cell.name))
    except FileNotFoundError:
        return None


def of(run):
    """``attribute`` of this run's trace, loaded and computed once per
    trace file."""
    path = trace_file(run)
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = attribute(T.load(path))
    return _CACHE[key]


def module_ms_per_iter(run, module: str, other: bool = False):
    """Device ms per iteration of ``module`` (``other``: without the
    kernel's operations); None where the module never ran."""
    a, its = of(run), run.counts.get("iterations")
    if a is None or not its or module not in a["module_s"]:
        return None
    t = a["module_other_s" if other else "module_s"][module]
    return 1e3 * t / its
