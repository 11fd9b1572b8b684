"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 perfbench/run.py --workload scan.desktop --seed 7 \
        --seconds 30 --trace 0

Set-up (counted in setup_s, from process start to the window's start):
build the native plane, write the cell's folder from --seed in spawned
JAX-free processes, boot the node with its API server, and run the
traffic's warm-up rounds, which compile or load from the checkout's
compile cache (.jax_cache) every program the window uses. The window
then runs whole rounds for --seconds; with --trace 1 it runs under the
JAX profiler and the line carries the per-layer metrics instead of the
end-to-end ones. After the window the node shuts down and the answers
are compared with the plain reference (check.py); each compared number
is printed beside its limit, last on stderr and last in the line.

Earlier stdout lines are JSON records of the run's phases. A run that
finds no TPU, or another number of chips than the cell asks for, exits
non-zero before any result. `--control NAME` swaps part of the timed
path for a broken one (controls.py), to show the check fails it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    from perfbench import harness, spec

    cell = spec.load_cell(args.workload)
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            T_PROCESS, require_chip=require_chip,
                            control=args.control)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
