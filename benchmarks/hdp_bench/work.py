"""Operations and bytes the HDP work needs, counted from shapes.

These are the least work the algorithm asks of the chip, not what the
implementation happens to do: a roofline share or an MFU built on them
is the measured time's distance from the least time the chip could
take. Every count is bytes-bound on a TPU by three orders of magnitude
(a live token moves about a kilobyte of table rows and needs about
ten operations per table slot), so the operation counts only decide the
bound where they would exceed it.

Conventions (int32 and float32 are 4 bytes):
  * a live token of the hdp_z sweep reads its word's table rows:
    with the kernel-prologue alias build the raw supports (vals, ids:
    2 * W * 4 bytes), with epilogue tables the packed rows (fpack and
    ipack, 2 * 2 * W * 4 bytes) and the 128-lane row holding q_a[v];
    its operations are 10 * W: the term-(a) weights, row total and alias
    partition, the term-(b) weights and row total, and the two draws;
  * every token position of the swept rows, live or not, moves its word
    id, mask, z in, three uniforms and z out (28 bytes);
  * every swept row writes its (R, 128) int32 topic histogram, with
    R = 8 * ceil(K / 1024).
"""

from __future__ import annotations

LANES = 128
POSITION_BYTES = 4 + 4 + 4 + 12 + 4


def hist_bytes(k: int) -> int:
    return -(-k // (8 * LANES)) * 8 * LANES * 4


def hdp_z(*, live: int, positions: int, rows: int, k: int, w: int,
          prologue: bool) -> dict:
    """One or more hdp_z sweeps: ``live`` live tokens over ``positions``
    token slots in ``rows`` document rows."""
    row = 2 * w * 4 if prologue else 2 * 2 * w * 4 + LANES * 4
    return {"flops": 10.0 * w * live,
            "bytes": float(live * row + positions * POSITION_BYTES
                           + rows * hist_bytes(k))}


def iteration(*, live: int, k: int, v: int, w: int) -> dict:
    """One streaming Gibbs iteration's least work: n read and the PPU
    draw's varphi and phi written (K x V, 12 bytes a cell), the W-wide
    word supports written for V words (vals and ids, 8 bytes a slot),
    and per live token its word id, z in and out and three uniforms
    (24 bytes); operations: the Poisson draw and normalisation (2 a
    cell), the top-W selection (log2 W a cell) and the sweep's 10 * W a
    live token."""
    log_w = max(w.bit_length() - 1, 1)
    return {"flops": float(k * v * (2 + log_w) + 10 * w * live),
            "bytes": float(k * v * 12 + v * w * 8 + live * 24)}


def foldin_request(*, tokens: int, bucket: int, k: int, w: int,
                   sweeps: int) -> dict:
    """The useful work of one fold-in request in the serving engine:
    ``sweeps`` sweeps of its ``bucket``-long slot row over epilogue
    tables (word id, mask, z in and out, three uniforms a position; the
    packed table rows and q_a a live token; its (K,) histogram out)."""
    per_sweep = (bucket * (4 + 1 + 8 + 12) + tokens * (2 * 2 * w * 4 + 4)
                 + k * 4)
    return {"flops": 10.0 * w * tokens * sweeps,
            "bytes": float(per_sweep * sweeps)}
