"""Scalability (paper §4/§6: partition the DB, mine per block): per-shard
work and memory vs number of MapReduce workers.

Runs HPrepost on 1/2/4/8 fake CPU devices (subprocess per world size,
each pinned to the CPU) and reports: wall time, per-shard tree nodes (the reducer's memory), and the
psum'd support correctness — the paper's "HPrepost memory << PrePost
memory" claim is the per-shard tree column. Every row is a CPU run and is
labelled so: its times say nothing about an accelerator.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_WORKER = textwrap.dedent(
    """
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
    import jax
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.core import encoding as enc
    from repro.core.ppc import build_ppc
    from repro.data.synth import load
    from repro.mining import MineSpec, MiningEngine

    D = int(sys.argv[1])
    rows, n_items = load("kosarak", scale=0.03)
    engine = MiningEngine(make_mesh((D, 1), ("data", "model")))
    spec = MineSpec(min_sup=0.008, max_k=4)
    min_count = spec.resolve(len(rows))
    engine.submit(rows, n_items, spec)                  # cold (compile)
    res = engine.submit(rows, n_items, spec)            # warm
    warm = res.wall_time_s

    # per-shard tree size (reducer memory model)
    fl = enc.build_flist(enc.item_support(rows, n_items), min_count)
    ranked = enc.rank_encode(rows, fl)
    shard_nodes = []
    per = (len(ranked) + D - 1) // D
    for d in range(D):
        block = ranked[d * per : (d + 1) * per]
        urows, w = enc.dedup_rows(block)
        shard_nodes.append(build_ppc(urows, w).n_nodes if len(urows) else 0)
    print(json.dumps({
        "platform": jax.default_backend(), "workers": D, "warm_s": warm, "n_itemsets": res.total_count,
        "max_shard_nodes": max(shard_nodes), "total_nodes_single": build_ppc(
            *enc.dedup_rows(ranked)).n_nodes,
    }))
    """
)


def run(out_path: str | None = None, worlds=(1, 2, 4, 8)) -> list[dict]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    # fake host devices exist only on the CPU backend, and a child must
    # never reach for an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    recs = []
    for d in worlds:
        out = subprocess.run(
            [sys.executable, "-c", _WORKER, str(d)],
            env=env, capture_output=True, text=True, timeout=560,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["platform"] == "cpu", rec
        recs.append(rec)
        print(
            f"[cpu] workers={d}: warm {rec['warm_s']:.2f}s | per-shard tree {rec['max_shard_nodes']} "
            f"nodes (single-node: {rec['total_nodes_single']}) | n={rec['n_itemsets']}"
        )
    if out_path:
        json.dump(recs, open(out_path, "w"), indent=1)
    return recs
