"""Bytes from u, the roofline share, and the table of peaks."""
import json

import pytest

from bench import roofline


def test_plane_bytes_from_u():
    # One plane of a 4096-document block is 128 uint32 words.
    assert roofline.plane_bytes(4096) == 512
    assert roofline.scan_bytes(102, 4096) == 102 * 512


def test_roofline_share():
    # 1,600,000 planes x 512 B = 819.2 MB: one millisecond at 819 GB/s.
    pct = roofline.roofline_pct(1_600_000, 4096, 0.004, "TPU v5 lite")
    assert pct == pytest.approx(100.0 * (819.2e6 / 819e9) / 0.004)


def test_peaks_of_v5e_and_unknown_kind(tmp_path):
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes"] == 16e9
    assert "TPU v5e" in json.loads(roofline.PEAKS_FILE.read_text())["source"]
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(roofline.UnknownDevice):
        roofline.roofline_pct(1, 4096, 1.0, "cpu")
