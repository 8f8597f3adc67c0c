import pytest

from benchmark import roofline


def test_peaks_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12 and p["hbm_bytes"] == 16e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_expected_distinct():
    # 1 draw: 1 key; draws >> keys: all keys
    assert roofline.expected_distinct(1, 1000) == pytest.approx(1.0)
    assert roofline.expected_distinct(1e6, 1000) == pytest.approx(1000.0)
    # K draws from K keys: K (1 - (1 - 1/K)^K), about 0.632 K
    assert roofline.expected_distinct(1000, 1000) == pytest.approx(
        1000 * (1 - (1 - 1e-3) ** 1000))


def test_update_cost_by_hand():
    # 2 steps of 4 events over 4 keys: 17 bytes per event in; per step
    # 4 (1 - (3/4)^4) = 2.734375 distinct accumulators, read and written
    ops, nbytes = roofline.update_cost(8, 2, 4)
    assert ops == 8
    assert nbytes == pytest.approx(8 * 17 + 2 * 2.734375 * 8)


def test_fire_cost_by_hand():
    assert roofline.fire_cost(1000) == (1000.0, 12_000)


def test_roofline_share():
    peak = roofline.peaks("TPU v5 lite")
    # 819 MB in 2 ms against 1 ms at the HBM peak: 50%
    assert roofline.roofline_pct(0, 819e6, 2e-3, peak) == pytest.approx(50)
    # bound by operations where they dominate
    assert roofline.roofline_pct(197e9, 0, 1e-3, peak) == pytest.approx(100)
