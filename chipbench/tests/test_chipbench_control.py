"""The control: the plain reference with its supports carried in
bfloat16 fails the comparison on every cell, at sizes where supports pass
256 as they do in the cells (the cells' own sizes are run on the chip)."""
import json

import pytest

from chipbench.harness import cells, control

# rows cut to at most this (kosarak 100,000; mushroom keeps its 8,124): any
# configuration has a size here, and supports pass 256 as in the cells
MAX_ROWS = 100_000


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 77])
@pytest.mark.parametrize("name", [w["name"] for w in cells.load_benchmark()["workloads"]])
def test_control_is_not_correct(name, seed):
    cell = cells.find_cell(name, cells.load_benchmark())
    cell.config = {**cell.config, "n_rows": min(cell.config["n_rows"], MAX_ROWS)}
    assert control.control_wrong_itemsets(cell, seed) > 0
