import peaks
import pytest


def test_v5e_peaks():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes_per_s == 819e9


def test_unknown_kind_raises():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peaks_for("cpu")
