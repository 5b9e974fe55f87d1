"""The roofline's arithmetic counts live rows, and the peaks table is
keyed by device kind."""
import pytest

from bench.lib import peaks, roofline


def test_work_counts_live_rows_not_padding():
    # two groups of 455 and 100 live rows at D=1152: codes + queries + params
    nbytes, ops = roofline.grouped_topk_work(rows=555, groups=2, dim=1152)
    assert nbytes == 555 * 1152 + 2 * (4 * 1152 + 8)
    assert ops == 2 * 555 * 1152


def test_least_time_is_bound_by_bytes_on_a_v5e():
    p = peaks.peaks("TPU v5 lite")
    nbytes, ops = roofline.grouped_topk_work(rows=1_000_000, groups=1, dim=1152)
    t = roofline.least_seconds(nbytes, ops, p)
    assert t == pytest.approx(nbytes / 819e9)
    assert t > ops / 393e12


def test_peaks_are_published_numbers_with_their_source():
    p = peaks.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    assert "TPU v5e" in p["source"]


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
