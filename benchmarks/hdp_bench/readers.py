"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader returns None where its run has nothing to read (no trace, no
kernel event, no span): the harness then leaves the metric out of the
result rather than print a made-up 0. Shares are percentages.
"""

from __future__ import annotations

from benchmarks.hdp_bench import peaks as P


def least_s(run, work: dict) -> float:
    return P.least_time_s(work["flops"], work["bytes"], run.peaks)[0]


def idle_share(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_s(run, name="hdp_z"):
    t = run.trace
    if not t or not t["kernel_events"].get(name):
        return None
    return t["kernel_s"][name]


def span_share(run, span: str):
    sec = run.spans.get(span)
    if sec is None or not run.counts.get("window_s"):
        return None
    return 100.0 * sec / run.counts["window_s"]


def occupancy(run):
    c = run.counts
    if not c.get("steps_in_window"):
        return None
    return 100.0 * c["completed_in_window"] * c["burnin"] / (
        c["steps_in_window"] * c["slots"])

