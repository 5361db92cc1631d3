"""One run of one benchmark cell on the chip.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json`` at the checkout's
root; a cell's configuration, traffic mix and metric readers are the files
of those names under ``chipbench/`` (see ``harness/cells.py``). The last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each number compared beside its limit (also the last lines of
standard error). Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench.harness.runner import main as run

    return run(args.workload, args.seed, args.seconds, bool(args.trace),
               t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
