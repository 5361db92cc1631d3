"""The control of the correctness check, and its readings.

The configurations state exact supports and no precision. The control is
the plain reference put in the program's place with its supports carried
in bfloat16, the step that would tempt a later change (one bf16 pass of
the MXU for the intersect contraction, which is exact only to 256): it
answers the cell's own requests, and the run's comparison must read it
as not correct. ``control_wrong_itemsets`` gives, for one seed, the
comparison's ``wrong_itemsets`` for the control's answers.
"""
from __future__ import annotations

from concurrent.futures import Future

import numpy as np

from chipbench.harness import data, reference
from chipbench.harness.client import ClosedLoopClient
from chipbench.harness.runner import wrong_itemsets


class _NoService:
    """Stands in for the service so the cell's own client makes the cell's
    own requests without serving them."""

    @staticmethod
    def _done():
        f = Future()
        f.set_result(None)
        return f

    def sweep(self, rows, n_items, spec, min_sups):
        return [self._done() for _ in min_sups]

    def append(self, *args, **kwargs):
        return self._done()

    def submit_stream(self, *args, **kwargs):
        return self._done()


def _requests(cell, seed: int, cycles: int):
    """(client, [(op, rows)]) for the mine and query requests of the cell's
    first ``cycles`` cycles on ``seed``."""
    base = data.base_database(cell.config)
    client = ClosedLoopClient(_NoService(), cell.config, cell.traffic, seed, base)
    client.warm_up()
    out = []
    for _ in range(cycles):
        for op in client.run_steps(cell.traffic["cycle"]):
            if op.kind == "mine":
                out.append((op, np.where(base >= 0, op.item_map[np.maximum(base, 0)], -1)))
            elif op.kind == "query":
                out.append((op, np.concatenate([client.stream_rows(k) for k in op.window])))
    return client, out


def control_wrong_itemsets(cell, seed: int, cycles: int = 1) -> int:
    """``wrong_itemsets`` of the control over the requests of the cell's
    first ``cycles`` cycles on ``seed``, each at the cell's own size."""
    client, reqs = _requests(cell, seed, cycles)
    wrong = 0
    for op, rows in reqs:
        count = reference.min_count(op.min_sup, len(rows))
        exact = reference.frequent_itemsets(rows, client.n_items, count, op.max_k)
        wrong += wrong_itemsets(reference.rounded_to_bfloat16(exact, count), exact)
    return wrong
