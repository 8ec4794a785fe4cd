"""The peak table: its v5e row and the error for an unknown device."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import peaks  # noqa: E402


def test_v5e_row():
    row = peaks.peak("TPU v5 lite")
    assert row["bf16_flops"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert "TPU v5e" in row["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", "tpu"])
def test_unknown_kind_raises(kind):
    with pytest.raises(KeyError, match="no peak table row"):
        peaks.peak(kind)
