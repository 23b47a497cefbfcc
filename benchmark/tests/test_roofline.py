"""The roofline's byte function and the peaks table."""

import pytest

from benchmark import roofline

V5E = "TPU v5 lite"


def test_bytes_at_4096_one_chip():
    vmem = roofline.peaks(V5E)["vmem_bytes"]
    block = roofline.block_nodes((4096, 4096), 1)
    assert block == (4097, 4097)
    per_iter = roofline.krylov_bytes_per_iter(block, vmem)
    assert per_iter == 3 * 4097 * 4097 * 4 - 128 * 2**20
    assert round(per_iter / 1e6, 1) == 67.2


@pytest.mark.parametrize("grid, chips", [((4096, 4096), 4), ((400, 600), 1)])
def test_no_bytes_where_the_vectors_fit(grid, chips):
    vmem = roofline.peaks(V5E)["vmem_bytes"]
    block = roofline.block_nodes(grid, chips)
    if chips == 4:
        assert block == (2049, 2049)
    assert roofline.krylov_bytes_per_iter(block, vmem) == 0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_roofline_reader_stays_silent_without_bytes():
    import types

    from benchmark.harness import load_module
    from conftest import ROOT
    import os

    reader = load_module(os.path.join(ROOT, "benchmark", "metrics",
                                      "pcg_roofline.py"))
    trace = {"busy_s": 4.0}
    view = types.SimpleNamespace(
        record={"iters": [3226]}, trace=trace, config={"grid": [400, 600]},
        device_kind=V5E, chips=1)
    assert reader.read(view) is None
    view.config = {"grid": [4096, 4096]}
    share = reader.read(view)
    assert share == pytest.approx(
        100 * 67207180 * 3226 / (4.0 * 819e9))
