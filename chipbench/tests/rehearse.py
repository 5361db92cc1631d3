"""The CPU rehearsal of one cell in a process of its own, for a cell on
more than one chip: JAX's CPU backend gets as many devices as the cell
asks for (the count is fixed when JAX starts, so a test's own process
cannot), and the run goes through ``runner.main`` with
``require_tpu=False``, the compile cache off.

    python -m chipbench.tests.rehearse <checkout> <workload> <seed> <trace> <chips>

The result line is the last line of standard output. Standard error has
the runner's log and, for each database the program prepares, ``data
shards: [...]``: the item occurrences that each shard of the ``data``
axis holds in its N-lists, in the order of the axis.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def run(checkout, workload: str, seed: int, trace: bool, chips: int,
        timeout: float = 600) -> subprocess.CompletedProcess:
    """Rehearse ``workload`` of ``checkout`` on ``chips`` CPU devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count={chips}".strip(),
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    return subprocess.run(
        [sys.executable, "-m", "chipbench.tests.rehearse", str(checkout), workload,
         str(seed), str(int(trace)), str(chips)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def data_shards(prepared) -> list[int]:
    """Item occurrences in each data shard's N-lists (``packed[..., 2]``
    holds each node's count); replicas along ``model`` are counted once."""
    import numpy as np

    by_index = {}
    for s in prepared.packed.addressable_shards:
        by_index[s.index[0].start or 0] = int(np.asarray(s.data)[..., 2].sum())
    return [by_index[k] for k in sorted(by_index)]


def _log_data_shards() -> None:
    from repro.core import hprepost

    prepare = hprepost.HPrepostMiner.prepare

    def logged(self, *args, **kwargs):
        prepared = prepare(self, *args, **kwargs)
        if prepared.packed is not None:
            print(f"data shards: {json.dumps(data_shards(prepared))}",
                  file=sys.stderr, flush=True)
        return prepared

    hprepost.HPrepostMiner.prepare = logged


def main(argv) -> int:
    checkout, workload, seed, trace, chips = argv
    import jax

    from chipbench.harness import runner

    if len(jax.devices()) != int(chips):
        print(f"rehearse: {len(jax.devices())} devices, not {chips}", file=sys.stderr)
        return 2
    runner.use_compile_cache = lambda root: "off"
    _log_data_shards()
    return runner.main(workload, int(seed), 0.2, bool(int(trace)),
                       t_process=time.perf_counter(), require_tpu=False,
                       root=pathlib.Path(checkout))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
