"""Time the blocked prompt-attention kernel alone at latent attention's
expanded shapes, one block shape against another.

    python tools/prompt_attention_sweep.py \
        --blocks 128x512x256,512x512x256 --tokens 1024,4096,16384

Q, K [1, heads, T, 192] and V [1, heads, T, 128] bfloat16 (the shapes
`latent_prefill_attention` hands the kernel for one layer of
openPangu-Ultra-MoE's 128 heads), drawn from `--seed`; each bucket `T` is run
with every position valid and with `Lengths` half of it.  A block shape is
`block_q x block_k x fold_rows` (`window_attention._prefill_attention`'s
arguments).  For each: the milliseconds a call (median of `--batches`
batches of `--calls` calls, each batch ended by `block_until_ready`), the
share of the roofline of ONE layer's valid causal pairs
(`benchmark/work_pangu_ultra_moe.prefill_attention_work` ÷ its layers, v5e
peaks from `benchmark/peaks.json`), and the largest difference of the valid
rows from the first block shape's, as a share of that one's largest value.

Times come only from a TPU: on another backend the script exits 2 unless
`--rehearse` is given, and then runs the kernel interpreted and prints no
time.  One JSON line a measurement, on stdout and appended to `--out`.
"""
import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import work_pangu_ultra_moe as work  # noqa: E402
from paddle_tpu.ops.kernels.window_attention import (  # noqa: E402
    _prefill_attention)

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "openpangu-ultra-moe-718b.json")


def _args():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--blocks", default="128x512x256,256x512x256,"
                   "512x512x256,512x512x512,256x1024x256")
    p.add_argument("--tokens", default="1024,2048,4096,8192,16384")
    p.add_argument("--heads", type=int, default=128)
    p.add_argument("--calls", type=int, default=5)
    p.add_argument("--batches", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", help="also append the JSON lines to this file")
    p.add_argument("--rehearse", action="store_true",
                   help="run interpreted off the TPU; no times")
    return p.parse_args()


def _roofline_ms(cfg, tokens):
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    peak = peaks["peaks"][jax.devices()[0].device_kind]
    flops, nbytes = work.prefill_attention_work(cfg, tokens)
    layers = cfg["num_hidden_layers"]
    return 1e3 * max(flops / layers / peak["bf16_flops_per_s"],
                     nbytes / layers / peak["hbm_bytes_per_s"])


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
    with open(CONFIG) as f:
        cfg = json.load(f)
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    shapes = [tuple(int(n) for n in s.split("x")) for s in a.blocks.split(",")]
    out = None
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        out = open(a.out, "a")
    for t in (int(n) for n in a.tokens.split(",")):
        keys = jax.random.split(jax.random.PRNGKey(a.seed + t), 3)
        q = jax.random.normal(keys[0], (1, a.heads, 1, t, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (1, a.heads, t, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (1, a.heads, t, dv), jnp.bfloat16)
        for length in (t, t // 2):
            lengths = jnp.full((1,), length, jnp.int32)
            first = None
            for bq, bk, fold in shapes:
                call = jax.jit(lambda q, k, v, n, bq=bq, bk=bk, fold=fold:
                               _prefill_attention(
                                   q, k, v, n, window=0, scale=d ** -0.5,
                                   block_q=bq, block_k=bk, fold_rows=fold,
                                   interpret=not tpu,
                                   name="latent_prefill_attention"))
                got = call(q, k, v, lengths).block_until_ready()
                rows = np.asarray(got[0, :, 0, :length], np.float32)
                if first is None:
                    first = rows
                apart = float(np.abs(rows - first).max()
                              / np.abs(first).max())
                line = {"tokens": t, "length": length, "heads": a.heads,
                        "block": f"{bq}x{bk}x{fold}", "apart": apart}
                if tpu:
                    per = []
                    for _ in range(a.batches):
                        t0 = time.perf_counter()
                        for _ in range(a.calls):
                            got = call(q, k, v, lengths)
                        got.block_until_ready()
                        per.append((time.perf_counter() - t0) * 1e3 / a.calls)
                    ms = statistics.median(per)
                    floor = _roofline_ms(cfg, length) * a.heads \
                        / cfg["num_attention_heads"]
                    line.update(ms=ms, roofline=100 * floor / ms)
                print(json.dumps(line), flush=True)
                if out:
                    out.write(json.dumps(line) + "\n")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
