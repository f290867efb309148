"""python3 benchmark/tools/cohere2_limit_readings.py --seed <n> [--requests 32]
    [--controls float32,int8,...]

The readings the limits of `serving_cohere2_moe` are set between, for the
cell with window and full attention layers, on the chip
(`moe_limit_readings.py`'s twin): serve `--requests` requests of the cell's
own mix through the cell's own system (`serving_cohere2_moe.Served`, HTTP,
all callers at once so that rows decode side by side and rings wrap), then
run the cell's own check (`check_against_reference` + `within_limits`)
over them once a control (`--controls`: all of `CONTROLS` by default) —
on the model's own weights (what a correct run shows: has to pass) and
under each control, which has to come out as NOT correct: the reference's
matrices rounded through int8 (the nearest precision below the bfloat16
the configuration states), the reference with the window mask taken off
the sliding layers, the reference without rotary positions, and the
reference with the ring kept wrongly from the prompt's end on
(`reference.RING_FAULTS`: the new column never written; the valid columns
miscounted past a wrap).  Prints every verdict with each reading beside its
limit, how the margins and the picks' shortfalls are distributed
(`moe_limit_readings.describe`), and each sampled sequence's decoded rows
against the engine's own programs.  Not a cell: nothing here is timed.
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

CELL = "command-a-plus-05-2026.rag_closed_ep8"
CONTROLS = (("float32", {}), ("int8", {"weights_as": "int8"}),
            ("window mask off", {"window": False}),
            ("no rotary", {"rotary": False}),
            ("ring stale", {"ring": "stale"}),
            ("ring unwrapped", {"ring": "unwrapped"}))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--controls", default=",".join(
        name.replace(" ", "_") for name, _ in CONTROLS))
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    asked = args.controls.split(",")
    if set(asked) - {name.replace(" ", "_") for name, _ in CONTROLS}:
        raise SystemExit(f"--controls {args.controls}: not of {CONTROLS}")
    import jax
    import numpy as np
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core import compile_cache
    from benchmark import harness, loadgen
    from benchmark import serving_cohere2_moe as cmd
    from benchmark.tools.moe_limit_readings import describe
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        raise SystemExit("not a TPU: nothing was run")
    compile_cache.initialize()
    cell = harness.Cell(args.root, args.cell)
    run = harness.Run(cell, args.seed, 0.0, 0, jax.devices(),
                      time.perf_counter(), harness.CompileClock(), print)
    with dg.guard():
        served = cmd.Served(run)
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
            print(f"served {len(done)} sequences, lengths "
                  f"{sorted(len(o) for o in outs)}; memory peak "
                  f"{harness.memory_peak_bytes(run.devices)} B", flush=True)
            rng = np.random.default_rng([args.seed, 11])
            for name, control in CONTROLS:
                if name.replace(" ", "_") not in asked:
                    continue
                per, t0 = [], time.perf_counter()
                got = cmd.check_against_reference(
                    served, done, args.seed, keep=per, **control)
                print(f"reference {name}: within_limits "
                      f"{cmd.within_limits(got)}: {got}; limits worst "
                      f"{cmd.TIE_SIGMA} mean {cmd.MEAN_SIGMA} shortfall "
                      f"{cmd.PICK_EPSILON} apart {cmd.PICKS_APART} hidden "
                      f"{cmd.HIDDEN_APART} decode {cmd.DECODE_APART}; "
                      f"{time.perf_counter() - t0:.1f} s", flush=True)
                describe(name, per, rng, np)
                print(f"  {name}: decoded rows against the engine's own "
                      "programs, a sequence (past the window, rows, median, "
                      "p90, max; the prefill's row): " + "; ".join(
                          f"{r['past']} {len(r['decode']) - 1} "
                          f"{np.median(r['decode'][1:]):.5f} "
                          f"{np.percentile(r['decode'][1:], 90):.5f} "
                          f"{r['decode'][1:].max():.5f}; "
                          f"{r['decode'][0]:.5f}" for r in per
                          if len(r["decode"]) > 1), flush=True)
        finally:
            served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
