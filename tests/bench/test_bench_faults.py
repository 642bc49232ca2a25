"""The faults of the timed path against each cell's own limits.

Each committed cell's limits file (``bench/limits/<cell>.json``) is put
on the tiny cell of the same mix.  There the sound program reads
``correct``, and each fault planted in the trainer reads not correct:
the limits that a cell is held to catch the faults, not only the tight
ones of the CPU tests."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402
from test_bench_correct import broken, run_cell  # noqa: E402

CELLS = {"resnet74.baseline": "tiny.baseline",
         "resnet74.e2train": "tiny.e2train"}
# the faults each cell's limits catch (PERF.md, Open questions: the
# baseline's do not yet catch a state returned unchanged)
FAULTS = [("resnet74.baseline", "half_batch"),
          ("resnet74.e2train", "half_batch"),
          ("resnet74.e2train", "unchanged")]


def cell_limits(cell):
    path = bench_tiny.REPO / "bench" / "limits" / f"{cell}.json"
    return json.loads(path.read_text())["limits"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(
        tmp_path_factory.mktemp("bench"),
        limits={tiny: cell_limits(cell) for cell, tiny in CELLS.items()})


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_sound_program_meets_the_cells_limits(root, cell):
    res = run_cell(root, CELLS[cell])
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == list(cell_limits(cell))


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_fault_fails_the_cells_limits(root, cell, fault):
    res = run_cell(root, CELLS[cell], trainer_cls=broken(fault))
    assert not res["correct"], res["checks"]
