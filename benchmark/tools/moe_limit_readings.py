"""python3 benchmark/tools/moe_limit_readings.py --seed <n> [--requests 64]

The readings the limits of `serving_moe_hybrid` are set between, for the
cell with routed experts, on the chip (`hybrid_limit_readings.py`'s twin):
serve `--requests` requests of the cell's own mix through the cell's own
system (`serving_moe_hybrid.Served`, HTTP, all callers at once so that
rows decode side by side), then run the cell's own check
(`check_against_reference` + `within_limits`) twice over them — on the
model's own weights (what a correct run shows: has to pass) and with the
reference's matrices rounded through int8 (the nearest precision below
the bfloat16 the configuration states: has to come out as NOT correct).
Prints both verdicts with every reading beside its limit, how the margins
and the picks' shortfalls are distributed, and what samples of fewer
sequences would have read; with `--unforced` also the margins of a
reference left to its own picks (the router's ties as noise: what the
limits were set between before the picks were given).  Not a cell:
nothing here is timed.
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

CELL = "nemotron-3-super-120b-a12b.chat_closed_ep4"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--unforced", action="store_true")
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core import compile_cache
    from benchmark import harness, loadgen
    from benchmark import serving_moe_hybrid as moe
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        raise SystemExit("not a TPU: nothing was run")
    compile_cache.initialize()
    cell = harness.Cell(args.root, args.cell)
    run = harness.Run(cell, args.seed, 0.0, 0, jax.devices(),
                      time.perf_counter(), harness.CompileClock(), print)
    with dg.guard():
        served = moe.Served(run)
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
                per = []
                got = moe.check_against_reference(
                    served, done, args.seed, weights_as=how, keep=per)
                print(f"reference weights {name}: within_limits "
                      f"{moe.within_limits(got)}: {got}; limits worst "
                      f"{moe.TIE_SIGMA} mean {moe.MEAN_SIGMA} shortfall "
                      f"{moe.PICK_EPSILON} apart {moe.PICKS_APART}",
                      flush=True)
                describe(name, per, rng, np)
            if args.unforced:
                per = moe.readings(served, done[:moe.SAMPLE], forced=False)
                describe("float32, picks not given", per, rng, np)
        finally:
            served.close()
    return 0


def describe(name, per, rng, np):
    """How the readings of `per` (a sequence each) are distributed, and
    what samples of half and a quarter as many would have read."""
    m = np.concatenate([r["margins"] for r in per])
    first = np.asarray([r["margins"][0] for r in per])
    line = (f"  {name}: {m.size} served tokens, margin in row sigmas: max "
            f"{m.max():.5f}, p99 {np.percentile(m, 99):.5f}, p90 "
            f"{np.percentile(m, 90):.5f}, mean {m.mean():.6f} (a sequence's "
            f"first answer token, the prefill's: {first.mean():.6f}); share "
            "of tokens over 0.005 / 0.02 / 0.1: " + " / ".join(
                f"{float((m > x).mean()):.4f}" for x in (0.005, 0.02, 0.1)))
    for n in (len(per) // 2, len(per) // 4):
        if n:
            draws = [rng.choice(len(per), n, replace=False)
                     for _ in range(200)]
            means = [np.concatenate([per[i]["margins"] for i in p]).mean()
                     for p in draws]
            line += (f"; over samples of {n}: mean margin "
                     f"{min(means):.6f} to {max(means):.6f}")
    if "shortfall" in per[0]:
        short = np.concatenate([r["shortfall"] for r in per], axis=1)
        line += ("; shortfall by layer, max: "
                 + " / ".join(f"{x:.6f}" for x in short.max(axis=1))
                 + f"; mean {short.mean():.7f}, p99 "
                 f"{np.percentile(short, 99):.6f}, p99.9 "
                 f"{np.percentile(short, 99.9):.6f}, p99.99 "
                 f"{np.percentile(short, 99.99):.6f}")
    print(line, flush=True)


if __name__ == "__main__":
    sys.exit(main())
