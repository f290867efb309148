"""python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once, in this one process, on the machine
it is started on.  Fails — non-zero, no result line — unless JAX's first
device is a TPU and the cell's chips are there.  The last line of stdout
is the one JSON object of the contract: with `--trace 0` the cell's
end-to-end metrics measured with the profiler off, with `--trace 1` (a run
of its own) its per-layer metrics and the breakdown.  Everything else worth
reading is on earlier lines.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()     # set-up counts from here

# JAX reads these when it is imported: persist every executable, however
# fast it compiled (the serving path is hundreds of sub-second per-op
# programs; under JAX's 1 s floor they recompile on every start, PR 21)
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    from benchmark import harness
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              args.trace, t_process=T_PROCESS)
    sys.stdout.flush()
    print(harness.result_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
