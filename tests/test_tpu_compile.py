"""Chip-free XLA:TPU + Mosaic compiles of kernels on the serving path, at
the published sizes: what interpret mode cannot show (tiling, VMEM, the
aliasing the compiler keeps).  The TPU compiler is installed beside JAX;
it compiles for a v5e that is described, not attached.  Nothing runs, so
nothing here is a time.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads libtpu (guide `on-chip-measurement` §2).
Keep every such test in THIS file.
"""
import os
import re

import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_state_update_of_granite_h_micro_compiles_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """Three consecutive Mamba layers' one-token updates over the donated
    `[36, 16, 64, 64, 128]` float32 state array of `granite-4.0-h-micro`:
    each is one Mosaic kernel aliased to the array, no copy of the array
    is left, and the program holds the array once."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import ssm
    from paddle_tpu.ops.registry import OpContext
    monkeypatch.setattr(ssm, "_interpret", lambda: False)
    layers, b, h, p, n = 36, 16, 64, 64, 128
    slab_shape = (layers, b, h, p, n)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def three_layers(slab, x, dt, a, bm, cm, d, bias, active):
        ctx = OpContext(seed=0, is_test=True)
        for index in (4, 5, 6):
            out = ssm.mamba2_state_update(
                {"X": x, "Dt": dt, "A": a, "B": bm, "C": cm, "D": d,
                 "State": slab, "DtBias": bias, "Lengths": active},
                {"slab_index": index}, ctx)
            slab, x = out["NewState"], out["Y"]
        return slab, x

    assert ssm._slab_update_fits(
        jax.ShapeDtypeStruct(slab_shape, jnp.float32), h, p, n)
    # x64 off, as the chip runs (conftest turns it on, and Mosaic refuses
    # the int64 block indices it makes)
    with jax.enable_x64(False):
        compiled = jax.jit(three_layers, donate_argnums=0).lower(
            sds(slab_shape, jnp.float32), sds((b, h, p), jnp.bfloat16),
            sds((b, h), jnp.bfloat16), sds((h,), jnp.float32),
            sds((b, 1, n), jnp.bfloat16), sds((b, 1, n), jnp.bfloat16),
            sds((h,), jnp.float32), sds((h,), jnp.float32),
            sds((b,), jnp.int32)).compile()
    text = compiled.as_text()
    slab = r"f32\[36,16,64,64,128\]"
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 3
    assert not re.findall(rf"= {slab}\S* copy\(", text)
    assert not re.findall(rf"= {slab}\S* fusion\(", text)
    assert "input_output_alias={ {0}: (0, {}, may-alias)" in text
    mem = compiled.memory_analysis()
    slab_bytes = int(np.prod(slab_shape)) * 4
    assert mem.alias_size_in_bytes == slab_bytes
    assert mem.temp_size_in_bytes < slab_bytes // layers  # under one entry


def test_state_update_at_128_heads_compiles_in_two_parts_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The one-token update over the donated `[5, 64, 128, 64, 128]`
    float32 state array of the decoder with 128 Mamba heads (a row's
    entry is 4.2 MB: four of them pass the VMEM budget, so the kernel
    takes it in two halves of 64 heads): still one Mosaic kernel a layer
    aliased to the array, no copy of it, the array held once."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import ssm
    from paddle_tpu.ops.registry import OpContext
    monkeypatch.setattr(ssm, "_interpret", lambda: False)
    layers, b, h, p, n, g = 5, 64, 128, 64, 128, 8
    slab_shape = (layers, b, h, p, n)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def two_layers(slab, x, dt, a, bm, cm, d, bias, active):
        ctx = OpContext(seed=0, is_test=True)
        for index in (1, 2):
            out = ssm.mamba2_state_update(
                {"X": x, "Dt": dt, "A": a, "B": bm, "C": cm, "D": d,
                 "State": slab, "DtBias": bias, "Lengths": active},
                {"slab_index": index}, ctx)
            slab, x = out["NewState"], out["Y"]
        return slab, x

    assert ssm._slab_parts(h, p, n) == 2
    with jax.enable_x64(False):
        compiled = jax.jit(two_layers, donate_argnums=0).lower(
            sds(slab_shape, jnp.float32), sds((b, h, p), jnp.bfloat16),
            sds((b, h), jnp.bfloat16), sds((h,), jnp.float32),
            sds((b, g, n), jnp.bfloat16), sds((b, g, n), jnp.bfloat16),
            sds((h,), jnp.float32), sds((h,), jnp.float32),
            sds((b,), jnp.int32)).compile()
    text = compiled.as_text()
    slab = r"f32\[5,64,128,64,128\]"
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 2
    assert not re.findall(rf"= {slab}\S* copy\(", text)
    assert not re.findall(rf"= {slab}\S* fusion\(", text)
    mem = compiled.memory_analysis()
    slab_bytes = int(np.prod(slab_shape)) * 4
    assert mem.alias_size_in_bytes == slab_bytes
    assert mem.temp_size_in_bytes < slab_bytes // layers


@pytest.mark.parametrize("rows", [64, 512], ids=["decode64", "prefill512"])
def test_grouped_experts_at_published_widths_compile_to_two_kernels(
        one_chip, no_compile_cache, monkeypatch, rows):
    """The held share of an expert layer at the published widths (128 of
    512 experts of 1,024 -> 2,688 -> 1,024, top 22): the sorted pairs go
    through two Mosaic grouped matmuls, and the program holds no dense
    [pairs, experts, ...] product (a dense expansion of a 64-row step
    would be 128x its 1,408 x 2,688 hidden rows)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import moe
    from paddle_tpu.ops.registry import OpContext
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    total, k, d, f, held = 512, 22, 1024, 2688, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def experts(x, picked, weights, w1, w2, lengths):
        out = moe.moe_grouped_experts(
            {"X": x, "Experts": picked, "Weights": weights, "W1": w1,
             "W2": w2, "Lengths": lengths},
            {"n_experts": total, "first_held": 128, "held": held},
            OpContext(seed=0, is_test=True))
        return out["Out"], out["Stats"]

    b, t = (rows, 1) if rows == 64 else (1, rows)
    with jax.enable_x64(False):
        compiled = jax.jit(experts).lower(
            sds((b, t, d), jnp.bfloat16), sds((b, t, k), jnp.int32),
            sds((b, t, k), jnp.float32), sds((held, d, f), jnp.bfloat16),
            sds((held, f, d), jnp.bfloat16), sds((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 2
    pairs = rows * k
    hidden_bytes = pairs * f * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * hidden_bytes


@pytest.fixture(scope="module")
def command_a_plus_programs():
    """The traced prefill (8,192-token bucket) and decode (32 rows) step
    Programs of the published `command-a-plus-05-2026` share, recorded from
    shapes alone: the model is built INSIDE `jax.eval_shape`, so its 9.47 GB
    of parameters are never allocated on this machine; what leaves is the
    Programs and the shapes of their feeds and parameters."""
    import json
    import jax
    import jax.numpy as jnp
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core.dtype import np_dtype
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.models import Cohere2MoeModel
    from paddle_tpu.serving.kv_pool import device_kv_arrays
    from paddle_tpu.serving.step_program import StepPrograms
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    sys.path.insert(0, here)
    from benchmark import serving_cohere2_moe
    with open(os.path.join(here, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        file = json.load(f)
    cfg = serving_cohere2_moe.model_config(file, file["engine"])
    slots = file["engine"]["max_slots_cap"]
    kv = device_kv_arrays(cfg.cache_spec(), file["engine"]["max_context"])
    got = {}

    def build():
        def zeros(shape, dtype):
            return Tensor(jnp.zeros(shape, dtype))

        with dg.guard():
            steps = StepPrograms(Cohere2MoeModel(cfg))
            got["prefill"] = steps._prefill.concrete_program(
                zeros((1, 8192), jnp.int32), zeros((1,), jnp.int32),
                zeros((1,), jnp.int32))
            got["decode"] = steps.decode_program(None).concrete_program(
                zeros((slots + len(steps.counters),), jnp.int32),
                zeros((slots,), jnp.int32), zeros((slots,), jnp.int32),
                *[zeros((a["layers"], slots) + tuple(a["shape"]),
                        np_dtype(a["dtype"])) for a in kv])
        return 0

    with jax.enable_x64(False):
        jax.eval_shape(build)
    return got, cfg, kv, slots


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_command_a_plus_step_programs_compile_inside_hbm(
        one_chip, no_compile_cache, monkeypatch, command_a_plus_programs,
        phase):
    """The 8,192-token prefill and the 32-row decode program of the
    published configuration's share, whole, for XLA:TPU + Mosaic: the
    weights are 9.47 GB, the prefill's temporaries stay under 2 GB (no
    `[T, T]` scores for all heads, no full-width rows of absent experts),
    and the decode program aliases every KV array to its result — 2.68 GB
    written in place, no copy left — so both fit the v5e's 16.9 GB beside
    the resident cache."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import moe, window_attention
    for module in (moe, window_attention):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    got, cfg, kv, slots = command_a_plus_programs
    cp = got[phase]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    block = cp.program.global_block()
    feeds = [sds(block.var(n).shape, block.var(n).dtype)
             for n in cp.feed_names]
    kept = tuple(f for i, f in enumerate(feeds) if i not in cp.donated)
    donated = tuple(feeds[i] for i in cp.donated)
    params = tuple(sds(t.shape, t._value.dtype) for t in cp.params.values())
    args = (sds((), jnp.uint32), params, kept, True) \
        + ((donated,) if donated else ())
    with jax.enable_x64(False):
        compiled = cp.composed().lower(*args).compile()
    mem = compiled.memory_analysis()
    weights = 2 * cfg.param_count()
    kv_bytes = sum(a["layers"] * slots * int(np.prod(a["shape"])) * 2
                   for a in kv)
    assert weights == 9_466_585_088 and kv_bytes == 2_684_354_560
    hbm = 16_909_336_064
    if phase == "prefill":
        assert not cp.donated and mem.alias_size_in_bytes == 0
        assert weights <= mem.argument_size_in_bytes < weights + (1 << 20)
        assert mem.temp_size_in_bytes < 2 << 30
        # beside the resident cache of 32 slots
        assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
            + mem.temp_size_in_bytes + kv_bytes < hbm
        text = compiled.as_text()
        assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                              text)) >= 4 + 6 + 8   # attention, rotary, gmm
        assert not re.findall(r"f32\[\d*,?128,8192,8192\]", text)
    else:
        assert len(cp.donated) == len(kv) == 4
        assert mem.alias_size_in_bytes == kv_bytes
        assert mem.temp_size_in_bytes < 1 << 29
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + mem.output_size_in_bytes - mem.alias_size_in_bytes < hbm
        text = compiled.as_text()
        for a in kv[::2]:       # no copy of a cache array is left
            shape = f"bf16\\[{a['layers']},{slots}," + ",".join(
                str(n) for n in a["shape"]) + "\\]"
            assert not re.findall(rf"= {shape}\S* copy\(", text)


def _hybrid_decode_programs(family, bounds):
    """The traced decode Programs of a published hybrid configuration at
    the cell's own slots, one a bound on the columns, recorded from shapes
    alone (the model is built inside `jax.eval_shape`): ({bound: program},
    config, [(shape, dtype)] of the cache arrays in the contract's order)."""
    import json
    import sys
    import jax
    import jax.numpy as jnp
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core.dtype import np_dtype
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.serving.kv_pool import device_kv_arrays, state_groups
    from paddle_tpu.serving.step_program import StepPrograms
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, here)
    if family == "granite":
        from paddle_tpu.models import GraniteHybridConfig
        from paddle_tpu.models import GraniteHybridModel as Model
        with open(os.path.join(here, "benchmark", "configs",
                               "granite-4.0-h-micro.json")) as f:
            file = json.load(f)
        cfg = GraniteHybridConfig.from_published(
            file, eos_id=file["eos_token_id"], bos_id=file["eos_token_id"],
            dtype=file["engine"]["dtype"])
    else:
        from benchmark import serving_moe_hybrid
        from paddle_tpu.models import NemotronHModel as Model
        with open(os.path.join(here, "benchmark", "configs",
                               "nemotron-3-super-120b-a12b.json")) as f:
            file = json.load(f)
        cfg = serving_moe_hybrid.model_config(file, file["engine"])
    slots = file["engine"]["max_slots_cap"]
    spec = cfg.cache_spec()
    arrays = [((a["layers"], slots) + tuple(a["shape"]), np_dtype(a["dtype"]))
              for a in device_kv_arrays(spec, file["engine"]["max_context"])]
    arrays += [((g["layers"], slots) + tuple(a["shape"]),
                np_dtype(a["dtype"]))
               for g in state_groups(spec) for a in g["arrays"]]
    got = {}

    def build():
        def zeros(shape, dtype):
            return Tensor(jnp.zeros(shape, dtype))

        with dg.guard():
            steps = StepPrograms(Model(cfg))
            for bound in bounds:
                got[bound] = steps.decode_program(bound).concrete_program(
                    zeros((slots + len(steps.counters),), jnp.int32),
                    zeros((slots,), jnp.int32), zeros((slots,), jnp.int32),
                    *[zeros(shape, dtype) for shape, dtype in arrays])
        return 0

    with jax.enable_x64(False):
        jax.eval_shape(build)
    return got, cfg, arrays


@pytest.mark.parametrize("family,bounds,kv_bytes,cache_bytes", [
    ("granite", (32, 1024), 134_217_728, 1_357_217_792),
    ("nemotron", (1024,), 67_108_864, 1_428_946_944)])
def test_hybrid_decode_programs_write_the_kv_where_it_lies(
        one_chip, no_compile_cache, monkeypatch, family, bounds, kv_bytes,
        cache_bytes):
    """The 16-row decode program of `granite-4.0-h-micro` (at the
    smallest bound, which reads one lane tile, and the largest, two blocks
    of 512) and the 64-row one of the nemotron share, whole, for XLA:TPU +
    Mosaic: every cache argument — K, V of the attention layers as well as
    the Mamba state — is aliased to its result, no copy of a KV array is
    left (the appends of the dense view copied it whole four times a
    step), and the temporaries stay two orders under the cache."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import moe, ssm, window_attention
    for module in (moe, ssm, window_attention):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    got, cfg, arrays = _hybrid_decode_programs(family, bounds)
    assert not cfg.kv_ring
    assert sum(2 * int(np.prod(shape)) for shape, _ in arrays[:2]) \
        == kv_bytes                             # K + V, bfloat16
    assert sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for shape, dtype in arrays) == cache_bytes

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    for bound, cp in got.items():
        assert cp.donated == (3, 4, 5, 6)
        block = cp.program.global_block()
        assert {op.attrs["columns"] for op in block.ops
                if op.type == "cached_decode_attention"} == {bound}
        feeds = [sds(block.var(n).shape, block.var(n).dtype)
                 for n in cp.feed_names]
        kept = tuple(f for i, f in enumerate(feeds) if i not in cp.donated)
        donated = tuple(feeds[i] for i in cp.donated)
        params = tuple(sds(t.shape, t._value.dtype)
                       for t in cp.params.values())
        with jax.enable_x64(False):
            compiled = cp.composed().lower(
                sds((), jnp.uint32), params, kept, True, donated).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.temp_size_in_bytes < 64 << 20
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + mem.output_size_in_bytes - mem.alias_size_in_bytes \
            < 16_909_336_064
        shape = "bf16\\[" + ",".join(str(n) for n in arrays[0][0]) + "\\]"
        assert not re.findall(rf"= {shape}\S* copy\(", compiled.as_text())
