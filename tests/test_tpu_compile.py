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


@pytest.mark.parametrize("tokens,k,d,f,held,total,activation,tilings", [
    (4096, 4, 2048, 1792, 32, 32, "silu_gated",
     [(128, 2048, 896), (128, 1792, 1024)]),
    (8192, 8, 4096, 4096, 16, 128, "silu_gated",
     [(128, 4096, 512), (128, 4096, 512)]),
    (1024, 22, 1024, 2688, 128, 512, "relu2",
     [(128, 1024, 896), (128, 2688, 1024)]),
], ids=["lfm2-prompt4096", "command-a-plus-block16384",
        "nemotron-prompt1024"])
def test_grouped_experts_compile_inside_vmem_with_the_whole_contraction(
        one_chip, no_compile_cache, monkeypatch, tokens, k, d, f, held,
        total, activation, tilings):
    """A prompt's expert layer at the published widths, each matmul's k
    tile the whole contraction: LFM2-8B-A1B's 4,096-token bucket (all 32
    experts held, 16,384 pairs on the plain path), command-a-plus's
    8,192-token bucket (16 of 128 held, held pairs first in blocks of
    16,384 rows) and nemotron's 1,024-token bucket in its latent width
    (128 of 512 held; W2's blocks take 13.96 MB of the 14 MiB budget).
    Mosaic refuses a block set past the scoped VMEM
    (`RESOURCE_EXHAUSTED`), so a tile the rule picks too wide fails here."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import monitor
    from paddle_tpu.ops.kernels import moe
    from paddle_tpu.ops.registry import OpContext
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    width = 2 * f if activation == "silu_gated" else f
    m = min(tokens * k, moe._BLOCK_ROWS)
    assert [moe.grouped_tiling(m, d, width),
            moe.grouped_tiling(m, f, d)] == tilings

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def experts(x, picked, weights, w1, w2, lengths):
        out = moe.moe_grouped_experts(
            {"X": x, "Experts": picked, "Weights": weights, "W1": w1,
             "W2": w2, "Lengths": lengths},
            {"n_experts": total, "first_held": 0, "held": held,
             "activation": activation}, OpContext(seed=0, is_test=True))
        return out["Out"], out["Stats"]

    monitor.stat_reset("ops.moe.gmm_whole_k")
    with jax.enable_x64(False):
        compiled = jax.jit(experts).lower(
            sds((1, tokens, d), jnp.bfloat16),
            sds((1, tokens, k), jnp.int32), sds((1, tokens, k), jnp.float32),
            sds((held, d, width), jnp.bfloat16),
            sds((held, f, d), jnp.bfloat16), sds((1,), jnp.int32)).compile()
    assert monitor.stat_get("ops.moe.gmm_whole_k") == 2
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          compiled.as_text())) == 2


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


def test_pangu_decode_program_reads_and_writes_the_latent_cache_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The 32-row decode program of the published `openpangu-ultra-moe-718b`
    share, whole, for XLA:TPU + Mosaic (the model built inside
    `jax.eval_shape`): the latent array [5, 32, 16384, 640] — 576 values a
    row in whole lanes — is aliased to its result and never copied (with
    576 lanes XLA:TPU laid the array out with the columns minor and copied
    it whole around every read), one `latent_decode_attention` kernel a
    layer, and the temporaries stay under 64 MB."""
    import json
    import sys
    import jax
    import jax.numpy as jnp
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core.dtype import np_dtype
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.models import PanguUltraMoeModel
    from paddle_tpu.ops.kernels import latent_attention, moe
    from paddle_tpu.serving.kv_pool import device_kv_arrays
    from paddle_tpu.serving.step_program import StepPrograms
    for module in (moe, latent_attention):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, here)
    from benchmark import serving_pangu_ultra_moe
    with open(os.path.join(here, "benchmark", "configs",
                           "openpangu-ultra-moe-718b.json")) as f:
        file = json.load(f)
    cfg = serving_pangu_ultra_moe.model_config(file, file["engine"])
    slots = file["engine"]["max_slots_cap"]
    (c,) = device_kv_arrays(cfg.cache_spec(), file["engine"]["max_context"])
    shape = (c["layers"], slots) + tuple(c["shape"])
    assert shape == (5, 32, 16384, 640)
    got = {}

    def build():
        def zeros(shape, dtype):
            return Tensor(jnp.zeros(shape, dtype))

        with dg.guard():
            steps = StepPrograms(PanguUltraMoeModel(cfg))
            got["decode"] = steps.decode_program(None).concrete_program(
                zeros((slots + len(steps.counters),), jnp.int32),
                zeros((slots,), jnp.int32), zeros((slots,), jnp.int32),
                zeros(shape, np_dtype(c["dtype"])))
        return 0

    with jax.enable_x64(False):
        jax.eval_shape(build)
    cp = got["decode"]
    assert cp.donated == (3,)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    block = cp.program.global_block()
    feeds = [sds(block.var(n).shape, block.var(n).dtype)
             for n in cp.feed_names]
    kept = tuple(f for i, f in enumerate(feeds) if i not in cp.donated)
    donated = tuple(feeds[i] for i in cp.donated)
    params = tuple(sds(t.shape, t._value.dtype) for t in cp.params.values())
    with jax.enable_x64(False):
        compiled = cp.composed().lower(
            sds((), jnp.uint32), params, kept, True, donated).compile()
    mem = compiled.memory_analysis()
    cache = int(np.prod(shape)) * 2
    assert mem.alias_size_in_bytes == cache
    assert mem.temp_size_in_bytes < 64 << 20
    weights = 2 * cfg.param_count()
    assert weights + cache <= mem.argument_size_in_bytes \
        < weights + cache + (1 << 20)
    text = compiled.as_text()
    assert not re.findall(r"= bf16\[5,32,16384,640\]\S* copy\(", text)
    assert len([ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln
                and "latent_decode_attention" in ln]) == 5


@pytest.mark.parametrize("t", [1024, 16384])
def test_latent_prompt_kernel_compiles_at_its_own_block_shape(
        one_chip, no_compile_cache, monkeypatch, t):
    """`latent_prefill_attention` over one layer of the published share's
    prompt, [1, 128, T, 192 | 128] bfloat16, for XLA:TPU + Mosaic at the
    block shape one query head a kv head takes (512 queries of the head a
    grid step, two folds of 256 against each 512-key tile; each head's
    whole K and V in VMEM at 16k): ONE kernel, no scores in HBM."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import latent_attention, window_attention
    from paddle_tpu.ops.registry import OpContext
    monkeypatch.setattr(latent_attention, "_interpret", lambda: False)
    assert window_attention.prompt_blocks(t, 1) == (512, 512, 256)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, k, v, lengths):
        return latent_attention.latent_prefill_attention(
            {"Q": q, "K": k, "V": v, "Lengths": lengths},
            {"scale": 192 ** -0.5}, OpContext(seed=0))["Out"]

    with jax.enable_x64(False):
        compiled = jax.jit(attend).lower(
            sds((1, 128, t, 192)), sds((1, 128, t, 192)),
            sds((1, 128, t, 128)), sds((1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len([ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln
                and "latent_prefill_attention" in ln]) == 1
    # at most the operands again, re-tiled for the kernel: scores of 128
    # heads at [T, T] would be 137 GB at 16k
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= mem.argument_size_in_bytes


def _float_ops(text):
    """The floating-point instructions of an optimized HLO module, as a
    multiset of (type, opcode, attributes without names, metadata or the
    backend's placement): what decides a program's arithmetic, whatever
    moves, copies or int results beside it."""
    import collections
    got = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = (\S+) (\S+)\(.*?\)(.*)", line)
        if not m or not m.group(1).startswith(("f32", "bf16")) \
                or m.group(2) in ("parameter", "bitcast", "copy",
                                  "copy-start", "copy-done", "tuple",
                                  "get-tuple-element"):
            continue
        attrs = re.sub(r"backend_config=.*$", "",
                       re.sub(r"metadata=\{.*?\}", "", m.group(3)))
        got[(m.group(1).split("{")[0], m.group(2),
             re.sub(r"[\w.-]+\.\d+", "_", attrs).strip())] += 1
    return got


@pytest.mark.parametrize("phase,size", [("prefill", 2048), ("decode", 1024)])
def test_lfm2_twins_compile_to_the_engine_programs_arithmetic(
        one_chip, no_compile_cache, monkeypatch, phase, size):
    """The LFM2 cell's check gives its reference the experts picked by
    TWINS of the engine's step programs (`serving_lfm2_moe._with_picks`:
    the same step with the picks as one result more) and needs the twins'
    logits to be the engine programs' to the bit.  Compiled whole for
    XLA:TPU + Mosaic at the published stage (a 2,048-token prompt bucket;
    64 rows at a 1,024-column bound), every floating-point instruction of
    the engine program is in the twin's and the twin has no other: the
    picks add int32 results and copies only."""
    import functools
    import json
    import sys
    import jax
    import jax.numpy as jnp
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core.dtype import np_dtype
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu.jit import StaticFunction
    from paddle_tpu.models import Lfm2MoeModel
    from paddle_tpu.ops.kernels import moe, ssm, window_attention
    from paddle_tpu.serving.kv_pool import device_kv_arrays, state_groups
    from paddle_tpu.serving.step_program import DECODE_CACHE_AT, StepPrograms
    for module in (moe, ssm, window_attention):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, here)
    from benchmark import serving_lfm2_moe
    with open(os.path.join(here, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        file = json.load(f)
    cfg = serving_lfm2_moe.model_config(file, file["engine"])
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
            model = Lfm2MoeModel(cfg)
            steps = StepPrograms(model)
            if phase == "prefill":
                args = [zeros((1, size), jnp.int32)] \
                    + [zeros((1,), jnp.int32)] * 2
                engine = steps._prefill
                twin = StaticFunction(
                    serving_lfm2_moe._with_picks(model.prefill_step, False),
                    layer=model, abstract_trace=True)
            else:
                args = [zeros((slots + len(steps.counters),), jnp.int32)] \
                    + [zeros((slots,), jnp.int32)] * 2 \
                    + [zeros(shape, dtype) for shape, dtype in arrays]
                engine = steps.decode_program(size)
                twin = StaticFunction(
                    serving_lfm2_moe._with_picks(functools.partial(
                        model.decode_step, columns=size), True),
                    layer=model, abstract_trace=True,
                    donate_args=range(DECODE_CACHE_AT,
                                      DECODE_CACHE_AT + len(arrays)))
            got["engine"] = engine.concrete_program(*args)
            got["twin"] = twin.concrete_program(*args)
        return 0

    with jax.enable_x64(False):
        jax.eval_shape(build)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    texts = {}
    for name, cp in got.items():
        block = cp.program.global_block()
        feeds = [sds(block.var(n).shape, block.var(n).dtype)
                 for n in cp.feed_names]
        kept = tuple(f for i, f in enumerate(feeds) if i not in cp.donated)
        donated = tuple(feeds[i] for i in cp.donated)
        params = tuple(sds(t.shape, t._value.dtype)
                       for t in cp.params.values())
        with jax.enable_x64(False):
            texts[name] = cp.composed().lower(
                sds((), jnp.uint32), params, kept, True,
                *((donated,) if donated else ())).compile().as_text()
    engine, twin = (_float_ops(texts[n]) for n in ("engine", "twin"))
    assert sum(engine.values()) > 2000
    assert engine == twin
    # the picks: 10 expert layers, top 4, one more int32 result
    rows = "1,2048" if phase == "prefill" else f"{slots},1"
    assert re.search(rf"s32\[10,{rows},4\]", texts["twin"])
    assert not re.search(rf"s32\[10,{rows},4\]", texts["engine"])
