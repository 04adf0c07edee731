"""The table of published peaks, keyed by device kind."""

import pytest

from benchmark.lib.peaks import UnknownDevice, peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_an_unknown_device_is_an_error(kind):
    with pytest.raises(UnknownDevice):
        peaks(kind)
