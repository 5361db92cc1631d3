"""The control: the plain reference with its supports carried in
bfloat16 fails the comparison on every cell, at sizes where supports pass
256 as they do in the cells (the cells' own sizes are run on the chip)."""
import json

import pytest

from chipbench.harness import cells, control

SIZES = {"kosarak": 100_000, "mushroom": 8124}  # mushroom at its own size


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 77])
@pytest.mark.parametrize("name", [w["name"] for w in cells.load_benchmark()["workloads"]])
def test_control_is_not_correct(name, seed):
    cell = cells.find_cell(name, cells.load_benchmark())
    cell.config = {**cell.config, "n_rows": SIZES[cell.config["name"]]}
    assert control.control_wrong_itemsets(cell, seed) > 0
