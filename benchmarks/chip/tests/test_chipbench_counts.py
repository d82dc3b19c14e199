"""Each lane's operation and byte counts against hand-computed shapes."""
import pytest

import _chipbench_path  # noqa: F401
from chipbench import harness
from chipbench.spec import Spec


@pytest.fixture(scope="module")
def spec():
    return Spec(_chipbench_path.ROOT)


def _shape(spec, cell):
    c = spec.cell(cell)
    return harness._shape(c.config, c.traffic, c.kinds)


def test_shapes_of_the_cells(spec):
    assert _shape(spec, "torus22.free") == {
        "draws": 8, "nodes": 10648, "edges": 63888, "classes": 1,
        "periods": 200, "records": 10, "measure": True}
    # The splice adds the 1 km class to the 2 m one.
    assert _shape(spec, "testbed.splice_mc") == {
        "draws": 1024, "nodes": 8, "edges": 56, "classes": 2,
        "periods": 400, "records": 20, "measure": True}


@pytest.mark.parametrize("lane,cell,flops,bytes_", [
    # 210 sweeps (200 periods + 10 records) of the (10648)^2 adjacency.
    ("tiled", "torus22.free", 2 * 8 * 10648 ** 2 * 210,
     4 * 10648 ** 2 * 210),
    # 420 sweeps of two 8x8 classes for 1024 draws; the adjacency once,
    # the ν and β records once.
    ("fused", "testbed.splice_mc", 2 * 1024 * 64 * 2 * 420,
     4 * 64 * 2 + 4 * 1024 * 8 * 20 * 2),
    # 3 operations per draw and edge per sweep; three slot tables once.
    ("sparse", "torus22.free", 3 * 8 * 63888 * 210,
     12 * 63888 + 4 * 8 * 10648 * 10 * 2),
])
def test_lane_counts(spec, lane, cell, flops, bytes_):
    got = spec.lanes()[lane].count(_shape(spec, cell))
    assert got == {"flops": flops, "bytes": bytes_}
    assert spec.lanes()[lane].TRACE_NAMES
