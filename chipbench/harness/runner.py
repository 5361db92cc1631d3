"""One run of one cell: set-up, the measured window, the check, the result.

``main`` is what ``chipbench/run.py`` calls. In order it: checks for the
chip (no TPU, or fewer chips than the cell asks for: exit 2 and no
result), builds the cell's mesh (``cells.Cell.mesh``), turns on the
compile cache, makes the cell's data from the seed
and warms up the cell's own shapes (``setup_s`` ends at the first timed
request), drives the window through ``MiningService`` (with ``--trace 1``
under the profiler and with the program's spans recorded), reads the
device memory peak, closes the service, checks every answer of the window
against the plain reference, and prints the result line.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

from chipbench.harness import cells, data, reference, workmodel
from chipbench.harness.client import ClosedLoopClient, Op

LIMITS = {"wrong_itemsets": 0, "wrong_answers": 0, "failed_ops": 0}


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""

    ops: list[Op]  # the window's requests, in order
    setup_s: float
    peak_bytes: int | None  # the fullest device's peak (see peak_bytes)
    device_kind: str
    spans: object = None  # the TraceRecorder of a traced run
    trace: dict | None = None  # normalized profiler trace of a traced run


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache(root) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``; every program is written to it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devs) -> int | None:
    """The fullest device's peak: ``peak_bytes_in_use`` (the buffers the
    runtime allocates) plus ``peak_bytes_reserved`` (the TPU runtime holds
    the programs' temporaries there, apart from the buffers, and keeps
    them reserved once a program has run)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


# ------------------------------------------------------------------ checking
def wrong_itemsets(got: dict, want: dict) -> int:
    """Missing + extra itemsets + itemsets with a wrong support."""
    missing = len(want.keys() - got.keys())
    extra = len(got.keys() - want.keys())
    wrong = sum(1 for k in want.keys() & got.keys() if got[k] != want[k])
    return missing + extra + wrong


def expected_answers(client: ClosedLoopClient, ops: list[Op]) -> list[tuple]:
    """(op, the exact answer, its rows, its min_count) for every mine and
    query op, from the plain reference."""
    n_items = client.n_items
    base = client.base_rows
    base_answers: dict = {}
    out = []
    for op in ops:
        if op.kind == "mine":
            key = (op.min_sup, op.max_k)
            count = reference.min_count(op.min_sup, len(base))
            if key not in base_answers:
                base_answers[key] = reference.frequent_itemsets(base, n_items, count, op.max_k)
            out.append((op, data.relabel_answer(base_answers[key], op.item_map), base, count))
        elif op.kind == "query":
            rows = np.concatenate([client.stream_rows(k) for k in op.window])
            count = reference.min_count(op.min_sup, len(rows))
            out.append((op, reference.frequent_itemsets(rows, n_items, count, op.max_k),
                        rows, count))
    return out


def check(ops: list[Op], expected: list[tuple]) -> dict:
    """The numbers compared, each with its limit."""
    n_itemsets = n_answers = 0
    for op, want, rows, count in expected:
        if op.error is not None:
            continue
        res = op.result
        d = wrong_itemsets(res.itemsets, want)
        rows_off = op.kind == "query" and (res.n_rows != len(rows) or res.min_count != count)
        n_itemsets += d
        n_answers += int(d > 0 or rows_off)
    failed = sum(op.error is not None for op in ops)
    values = {"wrong_itemsets": n_itemsets, "wrong_answers": n_answers + failed,
              "failed_ops": failed}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def work_model(expected: list[tuple], n_items: int) -> None:
    """Fill ``op.least_bytes`` for every mine and query op (traced runs)."""
    lengths: dict = {}
    for op, want, rows, count in expected:
        key = (id(rows), count)
        if key not in lengths:
            lengths[key] = workmodel.nlist_lengths(rows, n_items, count)
        # relabelled databases: the base database's N-list lengths under the
        # relabelling (the same tree, modulo F-list ties between items)
        base_ans = want
        if op.item_map is not None:
            to_base = np.argsort(op.item_map)
            base_ans = {tuple(int(to_base[i]) for i in s): v for s, v in want.items()}
        op.least_bytes = workmodel.least_bytes(rows, n_items, count, base_ans,
                                               lengths[key])
        op.level_bytes = workmodel.per_level_bytes(rows, n_items, count, base_ans,
                                                   lengths[key])


# ---------------------------------------------------------------------- run
def main(workload: str, seed: int, seconds: float, trace: bool, *,
         t_process: float, require_tpu: bool = True, root=cells.CHECKOUT,
         out=None) -> int:
    """One run; returns the exit code. ``root`` is the checkout holding
    ``BENCHMARK.json`` and ``chipbench/``; ``require_tpu=False`` is for the
    CPU rehearsal tests only."""
    out = out or sys.stdout
    bench = cells.load_benchmark(root)
    cell = cells.find_cell(workload, bench, root / "chipbench")
    import jax

    dev = device_info(jax.devices()[:cell.chips])
    t_jax = time.perf_counter() - t_process
    log(f"device: {dev}")
    if require_tpu and dev["platform"] != "tpu":
        log("chipbench: no TPU found; the benchmark runs only on the chip")
        return 2
    if len(jax.devices()) < cell.chips:
        log(f"chipbench: {workload} needs {cell.chips} chips, found {len(jax.devices())}")
        return 2
    from repro.launch.mesh import make_mesh

    mesh = make_mesh(cell.mesh, cells.MESH_AXES)
    log(f"mesh: {dict(mesh.shape)} over devices {[d.id for d in mesh.devices.flat]}")
    log(f"compile cache: {use_compile_cache(root)}")

    from chipbench.harness import tracing
    from repro.mining.service import MiningService
    from repro.mining.telemetry import trace as program_trace

    compiles = tracing.CompileCounter()
    t0 = time.perf_counter()
    base = data.base_database(cell.config)
    t_data = time.perf_counter() - t0
    service = MiningService(mesh=mesh)
    client = ClosedLoopClient(service, cell.config, cell.traffic, seed, base)
    spans = profiler = None
    try:
        t0 = time.perf_counter()
        compiles.counting = True
        client.warm_up()
        compiles.counting = False
        t_warm = time.perf_counter() - t0
        log(f"setup: process start to JAX's device list {t_jax:.6f} s, base data "
            f"{t_data:.6f} s, warm-up {t_warm:.6f} s ({compiles.count} traces "
            f"and compiles)")
        compiles.count = 0
        if trace:
            spans = tracing.span_bridge()
            program_trace.attach(spans)
            profiler = tracing.Profiler(str(root / "chipbench" / ".traces" / workload))
            profiler.start()
        compiles.counting = True
        gc_pauses = tracing.GcPauses()
        setup_s = time.perf_counter() - t_process
        with jax.profiler.TraceAnnotation(tracing.WINDOW_ANNOTATION):
            ops, w0, w1 = client.window(seconds)
        compiles.counting = False
        gc_pauses.close()
        if trace:
            profiler.stop()
        peak = peak_bytes(list(mesh.devices.flat))
        log(f"memory_stats of the first device after the window: "
            f"{mesh.devices.flat[0].memory_stats()}")
    finally:
        compiles.close()
        if profiler is not None:
            profiler.stop()
        program_trace.attach(None)
        service.close()
    log(f"window: {len(ops)} ops in {w1 - w0:.6f} s; compilations inside the "
        f"window: {compiles.count}; setup_s {setup_s:.6f}")
    for kind in ("mine", "query", "append"):
        lat = sorted(op.latency_s for op in ops if op.kind == kind)
        if lat:
            log(f"{kind}: {len(lat)} in the window; latency s min {lat[0]:.6f} "
                f"median {lat[len(lat) // 2]:.6f} max {lat[-1]:.6f}")
    log("mine and query latencies s, in order: " + " ".join(
        f"{op.latency_s:.6f}" for op in ops if op.kind != "append"))
    served = [op for op in ops if op.kind != "append" and op.error is None]
    if served:
        slow = max(served, key=lambda op: op.latency_s)
        times = {k: round(v, 6) for k, v in slow.result.stage_times_s.items()}
        log(f"slowest request: {slow.latency_s:.6f} s, service {slow.result.service_stats}, "
            f"wall {slow.result.wall_time_s:.6f} s, stages {times}")
    log(f"python gc in the window: {gc_pauses.count} collections of the oldest "
        f"generation, {gc_pauses.total_s:.6f} s, longest {gc_pauses.longest_s:.6f} s")
    del service
    gc.collect()

    expected = expected_answers(client, ops)
    checks = check(ops, expected)
    run = RunRecord(ops=ops, setup_s=setup_s, peak_bytes=peak,
                    device_kind=dev["kind"], spans=spans)
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": len(ops), "failed": checks["failed_ops"]["value"]}
    device = {**dev, "memory_peak_bytes": peak}
    if trace:
        from chipbench.harness import trace_reduce

        work_model(expected, client.n_items)
        run.trace = trace_reduce.normalize(profiler.xplane())
        busy, window = trace_reduce.busy_ns(run.trace)
        device.update(busy_s=busy / 1e9, window_s=window / 1e9)
        result["breakdown"] = trace_reduce.breakdown(run.trace)
        kernel_ns = trace_reduce.kernel_ns(run.trace, ("nlist_intersect_pallas",))
        log(f"work model: {sum(op.least_bytes or 0 for op in ops)} B once a request, "
            f"{sum(op.level_bytes or 0 for op in ops)} B once a level; intersect "
            f"kernel {kernel_ns / 1e9:.6f} s on the device")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.metric_reader(m["name"], root / "chipbench")(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device, checks=checks)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), file=out, flush=True)
    return 0
