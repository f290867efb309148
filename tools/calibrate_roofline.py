"""Calibrate the planner roofline against the LOCAL mesh
(static/planner.calibrate — the ISSUE 16 tentpole (d) loop closure).

The planner's roofline is a RANKING model: it divides walked FLOPs and
ring-accounted bytes by PEAK rates, so its absolute step times are
lower bounds and the argmax is all that is trusted.  This tool turns it
into a wall-clock estimator for one host class:

  1. builds a ladder of decision-table-shaped miniatures (fc towers,
     a plain batch ladder in one width/cache regime plus dp / ZeRO-1 /
     ZeRO-2×gm looped / ZeRO-2×gm scan-hoisted / ZeRO-3 at two
     widths) on the local mesh,
  2. prices each with `static.plan_program` pinned to exactly that knob
     point (verify off, calibration off — RAW roofline components), the
     compute leg denominated in a micro-measured host matmul rate,
  3. measures the same configuration's real per-step wall time
     (`Executor.run` loop for the looped rows; one
     `Executor.run_steps` scanned window / K for the hoisted row),
  4. fits `static.calibrate(pairs)` — per-class efficiencies for the
     compute / overlappable-wire / serial-wire legs plus a
     per-dispatch overhead intercept — and writes the fit + pairs to
     `perf_r05/roofline_calibration.json`.

`plan_program` auto-loads that file once its residual is under
`DEFAULT_CALIBRATION_RESIDUAL_PCT` (see `default_calibration`), so
checking the report in IS the flag flip that turns calibrated pricing
on for every `plan_program` call.

Usage:
    python tools/calibrate_roofline.py            # fit + write JSON
    python tools/calibrate_roofline.py --report   # + markdown table
    python tools/calibrate_roofline.py --out PATH # alternate output
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("PADDLE_TPU_VERIFY", "warn")
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

WORLD = 8
STEPS = 10
GM_K = 4


def _host_peak_flops():
    """Micro-measured matmul rate of THIS host (flops/s): the compute
    leg's denominator.  Peak-ish, not sustained — the fitted
    eff_compute absorbs the gap, but starting from the right order of
    magnitude keeps the coefficient inside the fit's (1e-4, 1] window."""
    import jax
    import jax.numpy as jnp
    n = 512
    a = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda x: x @ x)
    f(a).block_until_ready()          # compile outside the timing
    reps = 8
    t0 = time.time()
    for _ in range(reps):
        out = f(a)
    out.block_until_ready()
    dt = time.time() - t0
    return 2.0 * n ** 3 * reps / max(dt, 1e-9)


def _build(width, depth=4):
    import paddle_tpu.static as static
    from paddle_tpu.static import layers
    from paddle_tpu.core.program import _reset_unique_names
    _reset_unique_names()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, width])
        y = layers.data("y", [-1, 1])
        h = x
        for _ in range(depth):
            h = layers.fc(h, width, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        static.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def _apply(main, startup, spec):
    from paddle_tpu.distributed.sharding import shard_optimizer_states
    import paddle_tpu.static as static
    if spec.get("dp_shard"):
        shard_optimizer_states(main, startup, dp_degree=spec["dp_shard"],
                               stage=spec.get("zero_stage") or 1)
    if spec.get("grad_merge", 1) > 1:
        static.gradient_merge(main, spec["grad_merge"],
                              startup_program=startup)


def _predict(width, spec, batch, world, peak):
    """RAW roofline components of exactly this knob point."""
    import paddle_tpu.static as static
    main, startup, _ = _build(width)
    knobs = {"batch": (batch,),
             "remat": (False,),
             "dp_shard": (spec.get("dp_shard", 0),),
             "zero_stage": (spec.get("zero_stage", 0),),
             "grad_merge": (spec.get("grad_merge", 1),),
             "bucket_mb": (32,),
             "scan_hoist": (bool(spec.get("scan_hoist")),)}
    plan = static.plan_program(main, startup, world=world, knobs=knobs,
                               verify=False, calibration=False,
                               peak_flops=peak)
    c = plan.trace[0]
    for r in plan.trace:     # the pinned lattice still collapses a few
        if all(r[k] == v[0] for k, v in knobs.items() if k != "zero_stage"):
            c = r
            break
    return {"compute_ms": c["compute_ms"],
            "wire_overlap_ms": c["wire_overlap_ms"],
            "wire_serial_ms": c["wire_serial_ms"],
            "predicted_raw_ms": c["step_ms"]}


def _measure(width, spec, batch, world):
    """Best-of-3 measured per-step wall time of the same config (min
    discards scheduler noise on a shared host; the fit wants the
    repeatable floor, not the tail)."""
    import jax
    import numpy as np
    import paddle_tpu.static as static
    from paddle_tpu.distributed.compiled_program import CompiledProgram

    main, startup, loss = _build(width)
    _apply(main, startup, spec)
    gb = batch * world if world > 1 else batch
    rng = np.random.RandomState(0)
    hoist = bool(spec.get("scan_hoist"))
    k = spec.get("grad_merge", 1) if hoist else 1
    exe = static.Executor()
    scope = static.Scope()
    times = []
    with static.scope_guard(scope):
        prog = main
        if world > 1:
            prog = CompiledProgram(main).with_data_parallel(
                loss_name=loss.name,
                places=list(jax.devices())[:world])
        exe.run(startup)

        def one_feed(i):
            r = np.random.RandomState(i)
            return {"x": r.rand(gb, width).astype(np.float32),
                    "y": r.rand(gb, 1).astype(np.float32)}

        if hoist:
            window = {n: np.stack([one_feed(i)[n] for i in range(k)])
                      for n in ("x", "y")}
            exe.run_steps(prog, feed=window, fetch_list=[loss])  # warm
            for _ in range(3):
                t0 = time.time()
                outs = None
                for _ in range(max(1, STEPS // k)):
                    outs = exe.run_steps(prog, feed=window,
                                         fetch_list=[loss])
                np.asarray(outs[0])
                times.append((time.time() - t0) /
                             (max(1, STEPS // k) * k))
        else:
            f = one_feed(0)
            exe.run(prog, feed=f, fetch_list=[loss])          # warm
            for _ in range(3):
                t0 = time.time()
                for s in range(STEPS - 1):
                    exe.run(prog, feed=f, fetch_list=[])
                out = exe.run(prog, feed=f, fetch_list=[loss])
                np.asarray(out[0])
                times.append((time.time() - t0) / STEPS)
    return min(times) * 1e3   # ms


def _decode_pair(label, B, lc, W, tp, peak, weight_dtype="float32"):
    """One decode-step (memory-bound) calibration pair: the serving hot
    path is a tiny-FLOP, cache-dominated bucket, so its measured time is
    mostly dispatch intercept + mp wire — exactly the legs the training
    ladder under-constrains.  Prediction prices the REAL decode program
    (`serving.build_decode_program`): compute from the IR FLOP walk
    divided by tp (heads/MLP shard; the logits row is replicated but
    small at this geometry), serial wire from the per-layer Megatron
    collectives (two allreduces + the two KV gathers) over the ici
    rate.  Measurement drives `serving.TPShardedDecoder` — the same
    CompiledProgram the engine runs — best-of-3 over STEPS steps.

    At weight_dtype="int8" the program is first stamped through
    `slim.freeze_weights_int8` (the decoder applies the same stamp
    internally) and the int8 share of the walk is priced at
    `INT8_MXU_RATE` x the matmul rate — the v5e MXU claim the queued
    on-chip rows check; on this CPU host the decode step is
    intercept-dominated, so the fitted residual barely sees the rate
    and the pair's job is pinning the int8 wire/intercept shape."""
    import jax
    import numpy as np
    import paddle_tpu
    from paddle_tpu.models.gpt import GPTModel, GPTConfig
    from paddle_tpu.nn import MultiHeadAttention
    from paddle_tpu.serving.tp_decode import (TPShardedDecoder,
                                              build_decode_program,
                                              _param_map)
    from paddle_tpu.static.flops_analysis import (analyze_flops,
                                                  INT8_MXU_RATE)
    from paddle_tpu.static.planner import ici_bytes_per_chip

    cfg = GPTConfig(vocab_size=64, hidden_size=64, num_layers=2,
                    num_heads=4, max_position=256, dropout=0.0)
    prog, _, _ = build_decode_program(cfg, batch=B, cache_len=lc,
                                      width=W, tp_degree=tp)
    np.random.seed(0)
    m = GPTModel(cfg)
    m.eval()
    if weight_dtype == "int8":
        from paddle_tpu.slim.quantization import freeze_weights_int8
        from paddle_tpu.static.executor import Scope
        sd = m.state_dict()
        sc = Scope()
        for pname, key in _param_map(cfg).items():
            sc.set(pname, np.asarray(sd[key].numpy(), np.float32))
        freeze_weights_int8(prog, sc)
    fl = analyze_flops(prog, batch=B)
    fp_flops = fl["total_flops"] - fl.get("int8_flops", 0)
    compute_ms = ((fp_flops + fl.get("int8_flops", 0) / INT8_MXU_RATE)
                  / max(tp, 1) / peak * 1e3)
    # per-layer serial mp wire: ring allreduce moves 2(tp-1)/tp of the
    # [B, W, hidden] activation twice (o-proj + fc2), the two c_concat
    # KV gathers move (tp-1)/tp of it each
    act = B * W * cfg.hidden_size * 4
    frac = (tp - 1) / tp if tp > 1 else 0.0
    wire = cfg.num_layers * (2 * 2 * frac * act + 2 * frac * act)
    wire_serial_ms = wire / ici_bytes_per_chip() * 1e3

    world = 8 if tp > 1 else 1
    places = None if tp > 1 else [jax.devices()[0]]
    dec = TPShardedDecoder(m, tp_degree=tp, places=places,
                           weight_dtype=weight_dtype)
    ids = np.random.randint(0, cfg.vocab_size, (B, W)).astype(np.int64)
    k = np.random.randn(cfg.num_layers, B, cfg.num_heads, lc,
                        cfg.hidden_size // cfg.num_heads)
    k = (k * 0.1).astype(np.float32)
    pos = np.full((B,), lc, np.int64)
    mask = np.zeros((B, 1, W, lc + W), np.float32)

    def cache():
        return [MultiHeadAttention.Cache(paddle_tpu.to_tensor(k[li]),
                                         paddle_tpu.to_tensor(k[li]))
                for li in range(cfg.num_layers)]

    dec.forward(paddle_tpu.to_tensor(ids), cache=cache(),
                pos_offset=pos,
                attn_mask=paddle_tpu.to_tensor(mask))     # warm/compile
    times = []
    for _ in range(3):
        t0 = time.time()
        for _ in range(STEPS):
            out, _ = dec.forward(paddle_tpu.to_tensor(ids), cache=cache(),
                                 pos_offset=pos,
                                 attn_mask=paddle_tpu.to_tensor(mask))
        np.asarray(out.numpy())
        times.append((time.time() - t0) / STEPS)
    return {"label": label, "batch": B, "width": W, "world": world,
            "knobs": {"decode": True, "tp_degree": tp, "cache_len": lc,
                      "weight_dtype": weight_dtype},
            "compute_ms": compute_ms,
            "wire_overlap_ms": 0.0,
            "wire_serial_ms": wire_serial_ms,
            "predicted_raw_ms": compute_ms + wire_serial_ms,
            "measured_ms": round(min(times) * 1e3, 4)}


# (label, batch B, cache_len lc, step width W, tp degree, weight dtype)
# — the serving regime's calibration rows: decode steps from the
# engine's bucket lattice, tp=1 vs tp=2 so the per-world intercepts see
# both mesh classes from the memory-bound side too, plus the int8
# stamped pair of each mesh class so the calibrated roofline carries
# the INT8_MXU_RATE pricing leg
DECODE_SHAPES = [
    ("decode_b4_lc64_w1_tp1", 4, 64, 1, 1, "float32"),
    ("decode_b4_lc64_w1_tp2", 4, 64, 1, 2, "float32"),
    ("decode_b4_lc64_w4_tp2", 4, 64, 4, 2, "float32"),
    ("decode_b4_lc64_w1_int8_tp1", 4, 64, 1, 1, "int8"),
    ("decode_b4_lc64_w1_int8_tp2", 4, 64, 1, 2, "int8"),
]


# (label, width, batch, world, knob spec) — the looped/hoisted gm pair
# shares a rewrite so the hoist's measured win is apples-to-apples
SHAPES = [
    ("fc512_plain_b8", 512, 8, 1, {}),
    ("fc512_plain_b16", 512, 16, 1, {}),
    ("fc512_plain_b32", 512, 32, 1, {}),
    ("fc256_dp8_b16", 256, 16, WORLD, {}),
    ("fc512_dp8_b16", 512, 16, WORLD, {}),
    ("fc256_zero1_b16", 256, 16, WORLD,
     {"dp_shard": WORLD, "zero_stage": 1}),
    ("fc512_zero1_b16", 512, 16, WORLD,
     {"dp_shard": WORLD, "zero_stage": 1}),
    ("fc512_zero2_gm4_b16", 512, 16, WORLD,
     {"dp_shard": WORLD, "zero_stage": 2, "grad_merge": GM_K}),
    ("fc512_zero2_gm4_b16_hoist", 512, 16, WORLD,
     {"dp_shard": WORLD, "zero_stage": 2, "grad_merge": GM_K,
      "scan_hoist": True}),
    ("fc512_zero3_b16", 512, 16, WORLD,
     {"dp_shard": WORLD, "zero_stage": 3}),
]


def run_calibration():
    import jax
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.static.planner import calibrate

    peak = _host_peak_flops()
    pairs = []
    for label, width, batch, world, spec in SHAPES:
        pred = _predict(width, spec, batch, world, peak)
        measured = _measure(width, spec, batch, world)
        pairs.append(dict(pred, label=label, width=width, batch=batch,
                          world=world, knobs=dict(spec),
                          measured_ms=round(measured, 4)))
    for label, B, lc, W, tp, wdt in DECODE_SHAPES:
        pairs.append(_decode_pair(label, B, lc, W, tp, peak,
                                  weight_dtype=wdt))
    cal = calibrate(pairs)
    return cal, pairs, peak


def render_report(cal, pairs, peak):
    lines = [
        "| shape | compute ms | wire ovl ms | wire ser ms | "
        "raw pred ms | calibrated ms | measured ms | err % |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for p in pairs:
        est = cal.step_ms(p["compute_ms"], p["wire_overlap_ms"],
                          p["wire_serial_ms"])
        err = abs(est - p["measured_ms"]) / p["measured_ms"] * 100
        lines.append(
            "| {label} | {compute_ms:.4f} | {wire_overlap_ms:.4f} | "
            "{wire_serial_ms:.4f} | {predicted_raw_ms:.4f} | "
            "{est:.3f} | {measured_ms:.3f} | {err:.1f} |".format(
                est=est, err=err, **p))
    lines.append("")
    lines.append(
        f"Fit: eff_compute={cal.eff_compute:.4f}, "
        f"eff_wire_overlap={cal.eff_wire_overlap:.4f}, "
        f"eff_wire_serial={cal.eff_wire_serial:.4f}, "
        f"overhead_ms={cal.overhead_ms:.3f}; "
        f"mean |err| = {cal.residual_pct:.1f}% over {cal.n_pairs} "
        f"shapes (host matmul rate {peak / 1e9:.1f} GFLOP/s).")
    return "\n".join(lines)


def main():
    out_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perf_r05", "roofline_calibration.json")
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    t0 = time.time()
    cal, pairs, peak = run_calibration()
    wall = time.time() - t0
    cal.save(out_path, extra={
        "tool": "tools/calibrate_roofline.py",
        "host_platform": "cpu",
        "host_peak_flops": round(peak, 1),
        "world": WORLD,
        "pairs": pairs,
    })
    if "--report" in sys.argv:
        print(render_report(cal, pairs, peak))
    print(json.dumps({
        "metric": "roofline_calibration_residual_pct",
        "value": round(cal.residual_pct, 2),
        "coefficients": cal.to_dict(),
        "n_pairs": cal.n_pairs,
        "out": out_path,
        "wall_s": round(wall, 1),
    }))


if __name__ == "__main__":
    main()
