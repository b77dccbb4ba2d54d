"""The peak table: v5e as published, and no default for an unknown chip."""
import pytest

from bench import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks(kind)
