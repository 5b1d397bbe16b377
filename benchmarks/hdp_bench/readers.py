"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader returns None where its run has nothing to read (no trace, no
kernel event, no span): the harness then leaves the metric out of the
result rather than print a made-up 0. Shares are percentages.

A cell runs on ``run.cell.chips`` chips, and the readers count over them
so:

  least time      one chip's least time of the work (``least_chip_s``)
                  divided by the chips (``least_s``): the MFU readers
                  read the same for the same work at any chip count
  kernel and      chip-seconds, summed over the chips' device planes;
  module time     a roofline share divides one chip's least time by
                  them, so it reads the same work alike on any chips
  busy and idle   means over the chips' device planes

A trace with fewer device planes than the cell has chips (a chip that
ran nothing) reads nothing on the device: a mean over the chips that did
work would hide the idle one.
"""

from __future__ import annotations

from benchmarks.hdp_bench import peaks as P


def least_chip_s(run, work: dict) -> float:
    """Least seconds one chip needs for ``work``: its least chip-seconds."""
    return P.least_time_s(work["flops"], work["bytes"], run.peaks)[0]


def least_s(run, work: dict) -> float:
    """Least seconds the cell's chips need for ``work``, shared evenly."""
    return least_chip_s(run, work) / run.cell.chips


def device_trace(run):
    """The run's ``trace.reduce``, or None where it was not traced or
    holds fewer device planes than the cell has chips."""
    t = run.trace
    if not t or t["devices"] < run.cell.chips:
        return None
    return t


def idle_share(run):
    """Share of the window with no operation on a chip, mean over the
    cell's chips."""
    t = device_trace(run)
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_s(run, name="hdp_z"):
    """Chip-seconds of the kernel's events, summed over the cell's chips."""
    t = device_trace(run)
    if t is None or not t["kernel_events"].get(name):
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
