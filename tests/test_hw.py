"""The published-peaks table is keyed by ``device_kind`` and refuses kinds
it does not hold."""
import pytest

from repro import hw


def test_v5e_peaks_are_the_published_ones():
    p = hw.peaks("TPU v5 lite")
    assert p.flops == 197e12
    assert p.hbm_bytes_per_s == 819e9
    assert p.ici_bytes_per_s == 50e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        hw.peaks(kind)
