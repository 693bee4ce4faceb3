"""The table of the chip's peaks."""
import pytest

from peaks import peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
