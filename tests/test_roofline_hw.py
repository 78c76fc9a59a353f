"""Published peaks are keyed by device_kind; an unknown chip is an error."""

import pytest

from repro.roofline import hw


def test_v5e_peaks_are_the_published_ones():
    peak = hw.peaks("TPU v5 lite")
    assert peak.flops_bf16 == 197e12
    assert peak.hbm_bw == 819e9
    assert peak.ici_bw == 1600e9 / 8
    assert peak.hbm_bytes == 16 * 1024 ** 3
    assert "TPU v5e" in peak.source


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="cpu"):
        hw.peaks("cpu")
