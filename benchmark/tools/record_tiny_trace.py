"""python benchmark/tools/record_tiny_trace.py <out.xplane.pb.gz>

Records the small trace the tests of `benchmark/device_scopes.py` and
`benchmark/program_spans.py` read (tests/benchmark/data/README.md): the
tiny BERT of `tiny_train_v5e.xplane.pb`, two `Executor.run` and one
`Executor.run_steps` (K=4) fed through a `Prefetcher`, inside one
`bench/slice` annotation, Python tracer off, gzipped.  Run it through the
chip tool from the repo root; on the CPU it records a trace with no device
plane (a rehearsal of the script, not test data).
"""
import glob
import gzip
import itertools
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out):
    import jax
    import numpy as np

    import bench
    import paddle_tpu.static as static
    from benchmark import loadgen
    from paddle_tpu.reader.prefetcher import Prefetcher

    vocab, seq, batch, k = 512, 64, 8, 4
    main_p, startup, loss = bench.build_bert_base(
        vocab, seq, 128, 2, 2, batch, use_amp=True)
    main_p.random_seed = startup.random_seed = 7
    mix = {"seq_len": seq, "zipf_exponent": 1.0}
    singles = loadgen.training_batches(
        dict(mix, steps_per_dispatch=1), vocab, 7, batch)
    stacks = loadgen.training_batches(
        dict(mix, steps_per_dispatch=k), vocab, 7, batch)

    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope), tempfile.TemporaryDirectory() as tmp:
        exe.run(startup)
        exe.run(main_p, feed=next(singles), fetch_list=[loss])   # compile
        exe.run_steps(main_p, feed=next(stacks), fetch_list=[loss])
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench/slice"):
            with Prefetcher(itertools.islice(singles, 2)) as feeder:
                for feed in feeder:
                    out_ = exe.run(main_p, feed=feed, fetch_list=[loss],
                                   return_numpy=False)
                    np.asarray(out_[0])
            with Prefetcher(itertools.islice(stacks, 1)) as feeder:
                for feed in feeder:
                    out_ = exe.run_steps(main_p, feed=feed,
                                         fetch_list=[loss],
                                         return_numpy=False)
                    np.asarray(out_[0])
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(path, "rb") as src, gzip.open(out, "wb") as dst:
            dst.write(src.read())
    print(f"{jax.devices()[0].platform}: wrote {out} "
          f"({os.path.getsize(out)} bytes)")


if __name__ == "__main__":
    main(sys.argv[1])
