"""python3 benchmark/tools/hybrid_limit_readings.py --seed <n> [--requests 16]

The two readings `serving_cached.MEAN_SIGMA` is set between (and what
`TIE_SIGMA` guards), for the hybrid cell, on the chip: serve `--requests` requests of the cell's own mix
through the cell's own system (`serving_cached.Served`, HTTP, all callers
at once so that rows decode side by side), then teacher-force every served
sequence through the plain reference twice — on the model's own weights
(the served path's margin: what a correct run shows) and with the
reference's matrices rounded through int8 (the nearest precision below the
bfloat16 the configuration states: this reading has to come out as not
correct).  Prints the worst margin of each, in row sigmas, and the
distribution over served tokens.  Not a cell: nothing here is timed.
"""
import argparse
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "granite-4.0-h-micro.chat_closed"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core import compile_cache
    from benchmark import harness, loadgen, serving_cached
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        raise SystemExit("not a TPU: nothing was run")
    compile_cache.initialize()
    cell = harness.Cell(args.root, args.cell)
    run = harness.Run(cell, args.seed, 0.0, 0, jax.devices(),
                      time.perf_counter(), harness.CompileClock(), print)
    with dg.guard():
        served = serving_cached.Served(run)
        served.server.engine.default_timeout_s = 3600.0   # cold compiles
        try:
            stream = loadgen.closed_loop_requests(
                run.traffic, served.cfg["vocab_size"], run.seed)
            reqs = [next(stream) for _ in range(args.requests)]
            with ThreadPoolExecutor(len(reqs)) as pool:
                outs = list(pool.map(
                    lambda r: served.post(r.prompt, r.max_new, 3600.0),
                    reqs))
            done = list(zip(reqs, outs))
            rng = np.random.default_rng([args.seed, 11])
            for name, how in (("float32", None), ("int8", "int8")):
                per_seq = [serving_cached.margins(served, [d], how)
                           for d in done]
                m = np.concatenate(per_seq)
                # what a run of the cell reads: the worst margin and the
                # mean over a sample of SAMPLE served sequences
                n = min(serving_cached.SAMPLE, len(per_seq))
                picks = [rng.choice(len(per_seq), n, replace=False)
                         for _ in range(200)]
                worst = [max(per_seq[i].max() for i in p) for p in picks]
                means = [np.concatenate([per_seq[i] for i in p]).mean()
                         for p in picks]
                print(f"reference weights {name}: {m.size} served tokens, "
                      f"margin in row sigmas: max {m.max():.5f}, p99 "
                      f"{np.percentile(m, 99):.5f}, p95 "
                      f"{np.percentile(m, 95):.5f}, p90 "
                      f"{np.percentile(m, 90):.5f}, mean {m.mean():.6f}; "
                      "share of tokens over 0.005 / 0.01 / 0.02 / 0.05 / "
                      "0.1: " + " / ".join(
                          f"{float((m > x).mean()):.4f}"
                          for x in (0.005, 0.01, 0.02, 0.05, 0.1))
                      + f"; over samples of {n} sequences: worst margin min "
                      f"{min(worst):.5f} median {np.median(worst):.5f} max "
                      f"{max(worst):.5f}, mean margin min {min(means):.6f} "
                      f"median {np.median(means):.6f} max {max(means):.6f}",
                      flush=True)
        finally:
            served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
