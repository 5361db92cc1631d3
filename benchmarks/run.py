"""Benchmark runner — one section per paper table/figure.

  paper_runtime_memory : Figs 3-6 (runtime) + Figs 7-10 (memory)
  scaling              : §4 MapReduce block partitioning (workers sweep, CPU)
  kernels              : per-kernel micro-latency (CPU ref path)
  service              : cross-group overlap + snapshot warm-start (PR 4)
  roofline             : dry-run aggregation (EXPERIMENTS.md §Roofline)

Prints ``name,us_per_call,derived`` CSV lines per the harness contract.
Use ``--quick`` for a reduced sweep, ``--skip-scaling`` in constrained CI.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")
BENCH_PR = 10  # this PR's trajectory tag: emit_json writes BENCH_PR<n>.json


def emit_json(path: str | None = None, records=None, pr: int = BENCH_PR) -> str:
    """Write the machine-readable perf trajectory: kernel micro-bench rows,
    the host wave-planning vec-vs-loop comparison, end-to-end miner timings
    through one warm ``MiningEngine``, the service rows (cross-group
    overlap + snapshot warm-start), the streaming rows (append
    throughput vs full rebuild, segmented query latency, compaction cost),
    the distributed rows (1/2/4-worker scale-out + recovery time), and the
    telemetry rows (instrumented vs bare warm submit + the per-observation
    histogram/snapshot primitives).
    Future PRs diff their own emit against this file instead of re-deriving
    a baseline (``make bench-gate`` automates the diff).

    The output name is parameterized by ``pr`` (default: this PR), so each
    PR's trajectory lands in its own ``BENCH_PR<n>.json`` instead of
    overwriting its predecessor's."""
    from benchmarks.bench_distributed import run as distributed_run
    from benchmarks.bench_kernels import run as kernels_run
    from benchmarks.bench_service import run as service_run
    from benchmarks.bench_stream import run as stream_run
    from benchmarks.bench_telemetry import run as telemetry_run

    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..", f"BENCH_PR{pr}.json")
    if records is None:
        records = (kernels_run() + service_run(quick=True)
                   + stream_run(quick=True) + distributed_run(quick=True)
                   + telemetry_run(quick=True))
    payload = {
        "schema": "bench-trajectory-v1",
        "pr": pr,
        "records": [
            {"name": name, "us_per_call": round(us, 1), "note": note}
            for name, us, note in records
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return os.path.abspath(path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-scaling", action="store_true")
    args, _ = ap.parse_known_args()
    os.makedirs(RESULTS, exist_ok=True)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    print("name,us_per_call,derived")

    # --- paper tables (runtime + memory vs min-sup, 4 datasets)
    from benchmarks.bench_paper import run as paper_run

    recs = paper_run(os.path.join(RESULTS, "paper_tables.json"), quick=args.quick)
    for r in recs:
        tag = f"{r['dataset']}_sup{r['min_sup']}"
        print(f"fig3-6_runtime_hprepost_{tag},{r['hprepost_s']*1e6:.0f},n={r['n_itemsets']}")
        print(f"fig3-6_runtime_prepost_{tag},{r['prepost_s']*1e6:.0f},")
        print(f"fig3-6_runtime_fpgrowth_{tag},{r['fpgrowth_s']*1e6:.0f},")
        print(f"fig7-10_memory_hprepost_{tag},0,{r['hprepost_bytes']}B")
        print(f"fig7-10_memory_prepost_{tag},0,{r['prepost_bytes']}B")
        print(f"fig7-10_memory_fpgrowth_{tag},0,{r['fpgrowth_bytes']}B")

    # --- kernels + service + streaming (one BENCH_PR<n>.json trajectory)
    from benchmarks.bench_kernels import run as kernels_run
    from benchmarks.bench_service import run as service_run
    from benchmarks.bench_stream import run as stream_run

    recs = kernels_run()
    for name, us, note in recs:
        print(f"kernel_{name},{us:.0f},{note}")
    srecs = service_run(quick=args.quick)
    for name, us, note in srecs:
        print(f"{name},{us:.0f},{note}")
    trecs = stream_run(quick=args.quick)
    for name, us, note in trecs:
        print(f"{name},{us:.0f},{note}")
    from benchmarks.bench_distributed import run as distributed_run

    drecs = distributed_run(quick=args.quick)
    for name, us, note in drecs:
        print(f"{name},{us:.0f},{note}")
    from benchmarks.bench_telemetry import run as telemetry_run

    orecs = telemetry_run(quick=args.quick)
    for name, us, note in orecs:
        print(f"{name},{us:.0f},{note}")
    emit_json(records=recs + srecs + trecs + drecs + orecs)

    # --- scaling (subprocesses with fake devices)
    if not args.skip_scaling:
        from benchmarks.bench_scaling import run as scaling_run

        recs = scaling_run(os.path.join(RESULTS, "scaling.json"),
                           worlds=(1, 2, 4) if args.quick else (1, 2, 4, 8))
        for r in recs:
            print(
                f"scaling_cpu_workers{r['workers']},{r['warm_s']*1e6:.0f},"
                f"shard_nodes={r['max_shard_nodes']}/single={r['total_nodes_single']}"
            )

    # --- roofline aggregation (requires results/dryrun from repro.launch.dryrun)
    from benchmarks.roofline import load, summary

    recs = load()
    if recs:
        s = summary(recs)
        print(f"roofline_cells,{s['cells']},errors={s['errors']} skips={s['skips']}")
        for r in recs:
            if "skipped" in r or "error" in r:
                continue
            dom = max(r["t_compute"], r["t_memory"], r["t_collective"])
            print(
                f"roofline_{r['arch']}_{r['shape']}_{r['mesh']},"
                f"{dom*1e6:.1f},bottleneck={r['bottleneck']}"
            )


if __name__ == "__main__":
    main()
