"""The operation and byte counts, the table of peaks, and the refusal of
any device but a TPU."""

import pytest

from chipbench import spec
from chipbench.peaks import PEAKS, peaks_for
from chipbench.work import forest_bytes, query_work, roofline_s


def test_forest_bytes_counts_every_node_and_leaf():
    # depth 8: 255 internal nodes of feature (4 B), threshold (4 B) and
    # default direction (1 B), 256 leaves of 4 B
    assert forest_bytes(1, 8) == 255 * 9 + 256 * 4
    assert forest_bytes(500, 8) == 500 * 3319


@pytest.mark.parametrize("config,ops,nbytes", [
    ("higgs-xgb10", 11_000_000 * 10 * 8,
     11_000_000 * (28 * 4 + 4) + 10 * 3319),
    ("fraud-xgb500", 285_000 * 500 * 8,
     285_000 * (28 * 4 + 4) + 500 * 3319),
])
def test_query_work_of_each_cell(config, ops, nbytes):
    cfg = spec.config(spec.load_benchmark(), config)
    ds, f = cfg["dataset"], cfg["forest"]
    w = query_work(ds["rows"], ds["features"], f["trees"], f["depth"])
    assert w.ops == ops
    assert w.bytes == nbytes
    least, bound = roofline_s(w, peaks_for("TPU v5 lite"))
    # rows are read once and scored with one comparison per level: the
    # bytes set the bound at every cell's shapes
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 819e9)


def test_compute_bound_when_operations_dominate():
    from chipbench.work import Work
    least, bound = roofline_s(Work(ops=197e12, bytes=1.0),
                              peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_measurement_path_refuses_a_cpu_device():
    import jax
    from chipbench.run import NoChip, check_device
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(NoChip, match="not a TPU"):
        check_device(1)
