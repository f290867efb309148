"""Time the expert layers' grouped matmuls alone, the tiling before the
whole-contraction rule against `moe.grouped_tiling`'s.

    python tools/grouped_matmul_sweep.py \
        --configs lfm2-8b-a1b,command-a-plus-05-2026 \
        --held-pairs 2048,4096,8192,16384

Each served expert configuration's gate|up (W1 [held, D, 2F], or [held, D,
F] without a gate) and down (W2 [held, F, D]) matmul, as one chip holds
them (`expert_configs`: every configuration of `BENCHMARK.json` whose
cell's serving module builds a model with routed experts), bfloat16
operands drawn from `--seed`, over two kinds of call: a prompt's block of
`--rows` sorted rows of which `--held-pairs` land on the held experts (the rest in no
group, as `moe._held_first` passes them), and a decode step's
`rows x top-k` pairs of which the held experts' share lands here.  The held
pairs are spread over the held experts by a multinomial draw.  The old
tiling is ``(128, moe._tile(K), moe._tile(N))``.  For each: the
milliseconds a call (median of `--batches` batches of `--calls` calls, each
batch ended by `block_until_ready`), the share of the bf16 MXU peak
(`benchmark/peaks.json`) the held rows' FLOPs take in that time, and the
largest difference of the held rows from the old tiling's, as a share of
the old tiling's largest value.

Times come only from a TPU: on another backend the script exits 2 unless
`--rehearse` is given, and then runs the kernel interpreted and prints no
time (`--divide` shrinks widths and rows for it).  One JSON line a
measurement, on stdout and appended to `--out`.
"""
import argparse
import importlib
import importlib.util
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.ops.kernels import moe  # noqa: E402


def expert_configs(root=ROOT):
    """{configuration: its expert matmuls} for each configuration of the
    benchmark whose cell's traffic is served by `serve_closed_<x>`, which
    builds its model through `benchmark.serving_<x>.model_config`, and
    that holds routed experts: "w1" / "w2" [held, K, N] (`param_shapes`), "held" and "total" experts,
    "k" the top-k, "step_rows" a decode step's rows (the engine's
    `max_slots_cap`)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    out = {}
    for cell in manifest["workloads"]:
        with open(os.path.join(root, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            served_by = json.load(f).get("driver", "")
        module = "benchmark.serving_" + served_by.removeprefix(
            "serve_closed_")
        if not served_by.startswith("serve_closed_") or \
                importlib.util.find_spec(module) is None:
            continue
        make = getattr(importlib.import_module(module), "model_config", None)
        if make is None:
            continue
        with open(os.path.join(root, files[cell["config"]])) as f:
            cfg = json.load(f)
        model = make(cfg, cfg["engine"])
        shapes = model.param_shapes()
        w1 = [s for n, s in shapes.items() if n.endswith("experts.w1")]
        w2 = [s for n, s in shapes.items() if n.endswith("experts.w2")]
        if w1:
            out[cell["config"]] = dict(
                w1=w1[0], w2=w2[0], held=model.held_experts,
                total=model.n_routed_experts, k=model.num_experts_per_tok,
                step_rows=cfg["engine"]["max_slots_cap"])
    return out


def _args():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--configs", help="default: every one of "
                   "`expert_configs`")
    p.add_argument("--held-pairs", default="2048,4096,8192,16384")
    p.add_argument("--rows", type=int, default=moe._BLOCK_ROWS)
    p.add_argument("--calls", type=int, default=5)
    p.add_argument("--batches", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--divide", type=int, default=1,
                   help="divide widths, rows and pairs (for --rehearse)")
    p.add_argument("--out", help="also append the JSON lines to this file")
    p.add_argument("--rehearse", action="store_true",
                   help="run interpreted off the TPU; no times")
    return p.parse_args()


def _calls(cfg, a):
    """(kind, rows, held pairs) of the prompt blocks and the decode step."""
    step = cfg["step_rows"] * cfg["k"]
    out = [("prompt", a.rows // a.divide, int(h) // a.divide)
           for h in a.held_pairs.split(",")]
    return out + [("step", step, step * cfg["held"] // cfg["total"])]


def main():
    a = _args()
    device = jax.devices()[0]
    tpu = device.platform == "tpu"
    print(f"platform {device.platform} device_kind {device.device_kind}",
          flush=True)
    if not tpu and not a.rehearse:
        print("no TPU: times come only from one (--rehearse runs the "
              "kernel interpreted, untimed)", file=sys.stderr)
        return 2
    peak = None
    if tpu:
        with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
            peak = json.load(f)["peaks"][device.device_kind][
                "bf16_flops_per_s"]
    out = None
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        out = open(a.out, "a")
    rng = np.random.default_rng(a.seed)
    configs = expert_configs()
    for name in a.configs.split(",") if a.configs else configs:
        cfg = configs[name]
        keys = jax.random.split(jax.random.PRNGKey(a.seed), 3)
        for i, matrix in enumerate(("w1", "w2")):
            held, k, n = cfg[matrix]
            k, n = k // a.divide, n // a.divide
            w = jax.random.normal(keys[i], (held, k, n),
                                  jnp.bfloat16) * k ** -0.5
            for kind, m, held_pairs in _calls(cfg, a):
                rows = jax.random.normal(keys[2], (m, k), jnp.bfloat16)
                sizes = jnp.asarray(rng.multinomial(
                    held_pairs, [1 / cfg["held"]] * cfg["held"]), jnp.int32)
                old = (min(moe._TILE_M, m), moe._tile(k), moe._tile(n))
                new = moe.grouped_tiling(m, k, n)
                first = None
                for label, tiling in (("old", old), ("new", new)):
                    if label == "new" and new == old:
                        break
                    call = jax.jit(lambda r, w, s, t=tiling: gmm(
                        r, w, s, preferred_element_type=jnp.float32,
                        tiling=t, interpret=not tpu))
                    got = call(rows, w, sizes).block_until_ready()
                    held_rows = np.asarray(got[:held_pairs])
                    if first is None:
                        first = held_rows
                    apart = float(np.abs(held_rows - first).max()
                                  / max(np.abs(first).max(), 1e-30))
                    line = {"config": name, "matrix": matrix, "call": kind,
                            "rows": m, "held_pairs": held_pairs, "k": k,
                            "n": n, "tiling": label, "tile": list(tiling),
                            "k_tiles": -(-k // tiling[1]), "apart": apart}
                    if tpu:
                        per = []
                        for _ in range(a.batches):
                            t0 = time.perf_counter()
                            for _ in range(a.calls):
                                got = call(rows, w, sizes)
                            got.block_until_ready()
                            per.append((time.perf_counter() - t0)
                                       / a.calls)
                        sec = statistics.median(per)
                        line.update(ms=sec * 1e3, mxu=100 * 2 * held_pairs
                                    * k * n / sec / peak)
                    print(json.dumps(line), flush=True)
                    if out:
                        out.write(json.dumps(line) + "\n")
            del w
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
