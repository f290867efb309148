"""python benchmark/tools/find_knee.py --workload <open-loop cell> --rates 0.1,0.2,... [--seconds 60] [--seed 1]

The sweep that finds an open-loop cell's knee on the chip: the highest
arrival rate the system sustains with no growing backlog and no refusals.
Run once by hand when a cell is defined (never by the driver); the cell
file then fixes its rate at about four fifths of the knee and quotes this
tool's output.  One process builds and warms the system once and offers
each rate for `--seconds`, draining in between.
"""
import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    import paddle_tpu.dygraph as dg
    from benchmark import harness, loadgen, serving, stats
    from paddle_tpu.core import compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("find_knee: no TPU — a knee is a device number")
    compile_cache.initialize()
    cell = harness.Cell(ROOT, args.workload)
    serve_open = harness.load_module(ROOT, "drivers", "serve_open")
    clock = harness.CompileClock()
    rows = []
    with dg.guard():
        first = harness.Run(cell, args.seed, args.seconds, 0, jax.devices(),
                            T_PROCESS, clock, print)
        served = serving.Served(first)
        try:
            serving.warm_up(served, first)
            print(f"set-up {time.perf_counter() - T_PROCESS:.1f} s",
                  flush=True)
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                run = harness.Run(cell, args.seed + i, args.seconds, 0,
                                  jax.devices(), T_PROCESS, clock, print)
                requests = loadgen.open_loop_requests(
                    cell.traffic, served.cfg["vocab_size"], run.seed, rate,
                    args.seconds)
                serve_open._measure(run, served, requests,
                                    float(cell.traffic["drain_s"]))
                lat = run.samples["latency_s"]
                half = len(lat) // 2
                rows.append({
                    "rate_per_s": rate, "sent": run.attempted,
                    "failed": run.failed,
                    "s_per_answer_token":
                        run.end_to_end["serve_s_per_answer_token"],
                    "p50_s": stats.percentile(lat, 50),
                    "p90_s": stats.percentile(lat, 90),
                    # a growing backlog shows as later requests waiting
                    # longer than earlier ones
                    "p50_first_half_s": stats.percentile(lat[:half], 50),
                    "p50_second_half_s": stats.percentile(lat[half:], 50),
                    "steps_per_s": run.counters["gen.steps"] / args.seconds,
                    "occupancy": run.counters["gen.tokens"] / max(
                        1, run.counters["gen.steps"] * served.max_slots),
                    "memory_peak_bytes": harness.memory_peak_bytes(
                        jax.devices()[:1]),
                    "correct": bool(run.correct
                                    and all(run.checks.values()))})
                print("KNEE " + json.dumps(rows[-1]), flush=True)
        finally:
            served.close()
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
