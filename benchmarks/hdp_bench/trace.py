"""Reduction of a ``jax.profiler`` trace to device busy time, kernel
time and idle gaps.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
``load`` reads it with ``jax.profiler.ProfileData`` into plain tuples;
``reduce`` then works on those alone, so it is tested on a small trace
recorded on the chip and committed with the tests.

  device planes  planes named ``/device:<accelerator>:<n>``; the line
                 ``XLA Ops`` holds one event per executed operation,
                 named by its HLO text (``%hdp_z.1 = (...) custom-call(``);
                 ``XLA Modules`` holds the jitted program around it
  window         the harness's ``hdp_bench.window`` annotation on the
                 host; without it, the span of the device events
  busy           the union of operation intervals inside the window,
                 a mean over the device planes
  op names       the operation's own name, before `` = ``, after the
                 short name of its module: ``jit_local:hdp_z.1``
  kernel time    the summed duration of operations whose own name is the
                 kernel's name (``hdp_z``, the ``pallas_call`` name) or
                 that name with a ``.<n>`` suffix: chip-seconds, summed
                 over the device planes
  devices        the device planes with an operation; the readers take
                 nothing from a trace with fewer than the cell's chips
  idle gaps      the stretches of the window with no operation on a
                 device, each named by the innermost other harness
                 annotation (``hdp_bench.*``) that covers its middle
"""

from __future__ import annotations

import glob
import os

HARNESS_PREFIX = "hdp_bench."
WINDOW = HARNESS_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> list:
    """[(plane name, [(line name, [(event name, start_ns, dur_ns)])])]"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, float(e.start_ns),
                                  float(e.duration_ns)) for e in ln.events])
                      for ln in p.lines])
            for p in pd.planes]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def op_name(hlo: str) -> str:
    """``%hdp_z.1 = (s32[...]) custom-call(...)`` -> ``hdp_z.1``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def is_kernel(op: str, kernel: str) -> bool:
    return op == kernel or (op.startswith(kernel + ".")
                            and op[len(kernel) + 1:].isdigit())


def device_ops(planes: list) -> list:
    """Per device plane, its operations as (module:op, start, duration)."""
    import bisect

    out = []
    for name, lines in planes:
        if not is_device_plane(name):
            continue
        byname = dict(lines)
        evs = byname.get(OPS_LINE) or [e for _, evs in lines for e in evs]
        mods = sorted(byname.get(MODULES_LINE, []), key=lambda e: e[1])
        starts = [s for _, s, _ in mods]
        named = []
        for hlo, s, d in evs:
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0].split("(", 1)[0] if i >= 0 else ""
            named.append((f"{mod}:{op_name(hlo)}" if mod else op_name(hlo),
                          s, d))
        if named:
            out.append(named)
    return out


def annotations(planes: list) -> list:
    """Harness annotations on the host: [(name, start_ns, end_ns)]."""
    return [(n, s, s + d) for name, lines in planes
            if not is_device_plane(name)
            for _, evs in lines for n, s, d in evs
            if n.startswith(HARNESS_PREFIX)]


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(planes: list, kernels=("hdp_z",), top: int = 10) -> dict:
    """Busy, idle and kernel seconds over the traced window.

    Returns ``window_s``, ``busy_s`` (mean over devices), ``devices``
    (device planes with an operation), ``kernel_s`` and
    ``kernel_events`` per kernel name (summed over devices),
    ``device_ops`` (the ``top`` operations by total time, summed over
    devices) and ``idle_gaps`` (the ``top`` longest gaps of any device,
    named by host activity).
    ``None`` when the trace holds no device operation.
    """
    devs = device_ops(planes)
    if not devs:
        return None
    notes = annotations(planes)
    wins = [(s, e) for n, s, e in notes if n == WINDOW]
    if wins:
        lo, hi = wins[0]
    else:
        lo = min(s for evs in devs for _, s, _ in evs)
        hi = max(s + d for evs in devs for _, s, d in evs)
    window_s = (hi - lo) * 1e-9
    busy, gaps = [], []
    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    per_op: dict = {}
    for evs in devs:
        spans = _clip([(s, s + d) for _, s, d in evs], lo, hi)
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, s, d in evs:
            part = min(s + d, hi) - max(s, lo)
            if part <= 0:
                continue
            per_op[name] = per_op.get(name, 0.0) + part * 1e-9
            for k in kernels:
                if is_kernel(name.rsplit(":", 1)[-1], k):
                    kernel_s[k] += part * 1e-9
                    kernel_n[k] += 1
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    inner = [(n, s, e) for n, s, e in notes if n != WINDOW]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        cover = [(ce - cs, n) for n, cs, ce in inner if cs <= mid <= ce]
        label = min(cover)[1] if cover else "outside harness calls"
        named.append([label[len(HARNESS_PREFIX):]
                      if label.startswith(HARNESS_PREFIX) else label,
                      (e - s) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": sum(busy) / len(busy),
            "devices": len(devs), "kernel_s": kernel_s,
            "kernel_events": kernel_n,
            "device_ops": [[n, t] for n, t in ops], "idle_gaps": named}
