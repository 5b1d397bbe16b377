"""Work counts and the peaks table of the benchmark."""

import pytest

from benchmarks.hdp_bench import peaks, work


def test_hdp_z_counts_at_small_shape():
    # K=1000 -> an (8, 128) int32 histogram per row: 4096 bytes
    assert work.hist_bytes(1000) == 4096
    assert work.hist_bytes(1025) == 8192
    pro = work.hdp_z(live=10, positions=64, rows=2, k=1000, w=128,
                     prologue=True)
    assert pro == {"flops": 12800.0,
                   "bytes": float(10 * 1024 + 64 * 28 + 2 * 4096)}
    epi = work.hdp_z(live=10, positions=64, rows=2, k=1000, w=128,
                     prologue=False)
    assert epi["bytes"] == float(10 * 2560 + 64 * 28 + 2 * 4096)


def test_iteration_and_request_counts():
    it = work.iteration(live=100, k=8, v=16, w=4)
    assert it == {"flops": float(8 * 16 * 4 + 10 * 4 * 100),
                  "bytes": float(8 * 16 * 12 + 16 * 4 * 8 + 100 * 24)}
    rq = work.foldin_request(tokens=5, bucket=8, k=16, w=4, sweeps=2)
    assert rq["bytes"] == float(2 * (8 * 25 + 5 * 68 + 64))


def test_peaks_by_device_kind():
    v5e = peaks.for_kind("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops"] == 393e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.for_kind("TPU v99")


def test_least_time_names_its_bound():
    v5e = peaks.for_kind("TPU v5 lite")
    t, bound = peaks.least_time_s(1.0, 819e9, v5e)
    assert bound == "hbm" and t == pytest.approx(1.0)
    t, bound = peaks.least_time_s(197e12 * 2, 1.0, v5e)
    assert bound == "compute" and t == pytest.approx(2.0)
