"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

A device missing from the table is an error: a roofline share against a
guessed peak would be a made-up number.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def for_kind(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add them "
            f"to benchmarks/hdp_bench/peaks.py with their source") from None


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip needs, the bound that sets it)."""
    t_ops = flops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
