"""python3 benchmark/tools/lfm2_limit_readings.py --seed <n> [--requests 32]
    [--controls float32,int8,float8_e4m3fn]

The readings the limits of `serving_lfm2_moe` are set between, for the cell
with gated short convolutions and every expert of a layer held, on the chip
(`pangu_limit_readings.py`'s twin): serve `--requests` requests of the
cell's own mix through the cell's own system (`serving_lfm2_moe.Served`,
HTTP, all callers at once so that rows decode side by side), then run the
cell's own check (`check_against_reference` + `within_limits`) over them
once a control (`--controls`: all of `CONTROLS` by default) — on the
model's own weights (what a correct run shows: has to pass), with the
reference's matrices rounded through int8 (the nearest precision below the
bfloat16 the configuration states: has to come out as NOT correct) and
through float8 e4m3 (a reading beside them).  Prints every verdict with
each reading beside its limit and each sampled sequence's rows.  Not a
cell: nothing here is timed.  `--allow-cpu` runs it on the CPU, at the
sizes `--root`'s cell states.
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

CELL = "lfm2-8b-a1b.agent_closed_pp2"
CONTROLS = (("float32", {}),
            ("int8", {"weights_as": "int8"}),
            ("float8_e4m3fn", {"weights_as": "float8_e4m3fn"}))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--controls", default=",".join(n for n, _ in CONTROLS))
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    asked = args.controls.split(",")
    if set(asked) - {name for name, _ in CONTROLS}:
        raise SystemExit(f"--controls {args.controls}: not of {CONTROLS}")
    import jax
    import numpy as np
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core import compile_cache
    from benchmark import harness, loadgen
    from benchmark import serving_lfm2_moe as lfm
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        raise SystemExit("not a TPU: nothing was run")
    compile_cache.initialize()
    cell = harness.Cell(args.root, args.cell)
    run = harness.Run(cell, args.seed, 0.0, 0, jax.devices(),
                      time.perf_counter(), harness.CompileClock(), print)
    verdicts = {}
    with dg.guard():
        served = lfm.Served(run)
        served.server.engine.default_timeout_s = 3600.0   # cold compiles
        try:
            stream = loadgen.closed_loop_requests(
                run.traffic, served.cfg["vocab_size"], run.seed)
            reqs = [next(stream) for _ in range(args.requests)]
            t0 = time.perf_counter()
            with ThreadPoolExecutor(len(reqs)) as pool:
                outs = list(pool.map(
                    lambda r: served.post(r.prompt, r.max_new, 3600.0),
                    reqs))
            done = list(zip(reqs, outs))
            print(f"served {len(done)} sequences in "
                  f"{time.perf_counter() - t0:.1f} s (cold), lengths "
                  f"{sorted(len(o) for o in outs)}; memory peak "
                  f"{harness.memory_peak_bytes(run.devices)} B", flush=True)
            for name, control in CONTROLS:
                if name not in asked:
                    continue
                per, t0 = [], time.perf_counter()
                got = lfm.check_against_reference(
                    served, done, args.seed, keep=per, **control)
                verdicts[name] = lfm.within_limits(got)
                print(f"reference {name}: within_limits {verdicts[name]}: "
                      f"{lfm.describe(got)}; "
                      f"{time.perf_counter() - t0:.1f} s", flush=True)
                print(f"  {name}: rows a sequence (length, decoded rows, "
                      "median, p90, max; the prefill's row; margins p90, "
                      "max): " + "; ".join(
                          f"{r['length']} {len(r['rows']) - 1} "
                          f"{np.median(r['rows'][1:]):.5f} "
                          f"{np.percentile(r['rows'][1:], 90):.5f} "
                          f"{r['rows'][1:].max():.5f}; {r['rows'][0]:.5f}; "
                          f"{np.percentile(r['margins'], 90):.4f} "
                          f"{r['margins'].max():.4f}"
                          for r in per if len(r["rows"]) > 1), flush=True)
            print(f"memory peak after the checks "
                  f"{harness.memory_peak_bytes(run.devices)} B", flush=True)
        finally:
            served.close()
    return verdicts


if __name__ == "__main__":
    main()
