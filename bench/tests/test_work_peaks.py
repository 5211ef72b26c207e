import json

import pytest

import registry


def test_lu_work():
    lu = registry.work("lu")
    assert lu.flops(3000) == pytest.approx(2 / 3 * 3000 ** 3)
    assert lu.bytes_moved(1000) == 4e6
    assert lu.bytes_moved(1000, itemsize=8) == 8e6


def test_peaks_of_a_known_kind_name_their_source():
    p = registry.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_kind_raises(kind):
    with pytest.raises(KeyError):
        registry.peaks(kind)


def test_roofline_of_the_n8000_cell_is_compute_bound_and_small():
    """What the roofline reads at the smoke timing (5.2 s per call): the
    least time is the operations', 1.73 ms."""
    lu = registry.work("lu")
    p = registry.peaks("TPU v5 lite")
    least = max(lu.flops(8000) / p["flops_per_s"],
                lu.bytes_moved(8000) / p["hbm_bytes_per_s"])
    assert least == pytest.approx(1.733e-3, rel=1e-3)
    assert 100 * least / 5.2 < 0.05


def test_peaks_table_is_plain_json():
    raw = (registry.ROOT / "bench" / "peaks.json").read_text()
    for kind, p in json.loads(raw).items():
        assert {"flops_per_s", "hbm_bytes_per_s", "source"} <= set(p), kind
