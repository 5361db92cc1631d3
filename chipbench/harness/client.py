"""The one traffic generator: a closed-loop client of ``MiningService``.

A traffic file (``traffic/<name>.json``) is data for this client:

    {"about": "...",              # what the mix stands for, for readers
     "stream": {"window_batches": 3, "batch_rows": 2031},
     "warmup": [step, ...],        # once, before the window (set-up)
     "cycle":  [step, ...]}        # repeated; whole cycles only

Steps:

- ``{"op": "mine", "database": "fresh" | "resident", "min_sups": [...],
  "max_k": k}``: one ``MiningService.sweep`` (one ``submit`` per
  threshold). ``fresh`` mines a new database each time: the base database
  with its rows shuffled and its item ids permuted, made by the client
  before it submits. ``resident`` mines one such database, made once at
  set-up, every time.
- ``{"op": "append", "count": n}``: n stream appends, each waited for
  before the next, of the next n batches of the stream's sequence, or
  with ``"at": i`` of batches i, i+1, ... (the warm-up's own batches,
  beyond any run's reach). Batch i is the configuration's batch i (fixed
  by its data seed, like a dataset file) with its item ids permuted by
  the run's own permutation: no batch repeats within a run, every run
  ingests new content, and every run does the same work.
- ``{"op": "query", "min_sup": s, "max_k": k}``: one ``submit_stream``
  over the stream's current window.

Each request is timed from its call to its Future resolving; the client's
own work between requests (making databases and batches) is outside those
times.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench.harness import data
from chipbench.harness.tracing import client_span

STREAM = "bench"


@dataclasses.dataclass
class Op:
    """One request the window made, and what its answer must be."""

    kind: str  # "mine" | "append" | "query"
    t_start: float = 0.0
    t_done: float = 0.0
    result: object = None
    error: BaseException | None = None
    min_sup: float | None = None
    max_k: int | None = None
    item_map: np.ndarray | None = None  # mine: base item id -> served id
    window: tuple = ()  # query: the stream batches it covers
    least_bytes: int | None = None  # filled by the work model (traced runs)
    level_bytes: int | None = None  # the same, each N-list read once a level

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_start


class ClosedLoopClient:
    """Drives one service with one traffic mix; one request in flight."""

    def __init__(self, service, cfg: dict, traffic: dict, seed: int,
                 base_rows: np.ndarray):
        from repro.mining import MineSpec

        self._MineSpec = MineSpec
        self.service = service
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed % 2**63
        self.n_items = int(cfg["n_items"])
        self.base_rows = base_rows
        self._fresh = 0  # fresh databases made so far
        self._resident = None  # (rows, item_map), made on first use
        self._appended: list[int] = []  # stream batches so far, in order
        self._next_batch = 0  # the stream sequence's next batch
        self._stream_map = np.random.default_rng([self.seed, 3]).permutation(
            self.n_items).astype(np.int32)
        st = traffic.get("stream", {})
        self.window_batches = int(st.get("window_batches", 0))
        self.batch_rows = int(st.get("batch_rows", 0))

    # ------------------------------------------------------------ databases
    def _database(self, which: str):
        if which == "resident":
            if self._resident is None:
                self._resident = data.relabelled(
                    self.base_rows, self.n_items, np.random.default_rng([self.seed, 0]))
            return self._resident
        if which == "fresh":
            self._fresh += 1
            with client_span("make_database"):
                return data.relabelled(self.base_rows, self.n_items,
                                       np.random.default_rng([self.seed, 1, self._fresh]))
        raise ValueError(f"unknown database {which!r}")

    def stream_rows(self, i: int) -> np.ndarray:
        """The rows of stream batch ``i`` as this run appends them."""
        seed = int(self.cfg["assumed"]["data_seed"])
        return data.relabel(data.stream_batch(self.cfg, (seed, i), self.batch_rows),
                            self._stream_map)

    # ---------------------------------------------------------------- steps
    def _mine(self, step: dict) -> list[Op]:
        rows, item_map = self._database(step["database"])
        sups = [float(s) for s in step["min_sups"]]
        spec = self._MineSpec(max_k=step.get("max_k"))
        ops = [Op("mine", min_sup=s, max_k=step.get("max_k"), item_map=item_map)
               for s in sups]
        with client_span("mine"):
            t0 = time.perf_counter()
            futures = self.service.sweep(rows, self.n_items, spec, sups)
            self._settle(futures, ops, t0)
        return ops

    def _append(self, step: dict) -> list[Op]:
        from repro.mining.stream import StreamSpec

        count = int(step["count"])
        if "at" in step:
            first = int(step["at"])
        else:
            first, self._next_batch = self._next_batch, self._next_batch + count
        ops = []
        spec = self._MineSpec(max_k=None)
        sspec = StreamSpec(window_batches=self.window_batches)
        for i in range(first, first + count):
            with client_span("make_batch"):
                rows = self.stream_rows(i)
            op = Op("append")
            with client_span("append"):
                t0 = time.perf_counter()
                fut = self.service.append(rows, self.n_items, stream=STREAM,
                                          spec=spec, stream_spec=sspec)
                self._settle([fut], [op], t0)
            self._appended.append(i)
            ops.append(op)
        return ops

    def _query(self, step: dict) -> list[Op]:
        op = Op("query", min_sup=float(step["min_sup"]),
                max_k=step.get("max_k"),
                window=tuple(self._appended[-self.window_batches:]))
        spec = self._MineSpec(min_sup=op.min_sup, max_k=op.max_k)
        with client_span("query"):
            t0 = time.perf_counter()
            fut = self.service.submit_stream(spec, stream=STREAM)
            self._settle([fut], [op], t0)
        return [op]

    @staticmethod
    def _settle(futures, ops: list[Op], t0: float) -> None:
        """Wait for every Future; stamp each op with the call time and the
        time its own Future resolved."""
        for op, fut in zip(ops, futures):
            op.t_start = t0
            fut.add_done_callback(
                lambda _f, op=op: setattr(op, "t_done", time.perf_counter()))
        for op, fut in zip(ops, futures):
            try:
                op.result = fut.result()
            except Exception as e:  # a failed request is counted, not raised
                op.error = e
            if not op.t_done:  # resolved before the callback was attached
                op.t_done = time.perf_counter()

    def run_steps(self, steps: list[dict]) -> list[Op]:
        run = {"mine": self._mine, "append": self._append, "query": self._query}
        ops: list[Op] = []
        for step in steps:
            ops += run[step["op"]](step)
        return ops

    def warm_up(self) -> None:
        self.run_steps(self.traffic.get("warmup", []))

    def window(self, seconds: float) -> tuple[list[Op], float, float]:
        """Whole cycles, started while ``seconds`` have not yet passed.
        -> (ops, window start, window end) on the host's perf_counter."""
        ops: list[Op] = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ops += self.run_steps(self.traffic["cycle"])
        return ops, t0, time.perf_counter()
