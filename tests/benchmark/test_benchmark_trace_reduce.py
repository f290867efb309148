"""The reduction from a trace to numbers: the interval arithmetic on
hand-built event lists, and the whole of it on a small trace recorded on
the v5e (tests/benchmark/data/README.md)."""
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_total_clip_subtract_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9), (20, 30)])
    assert busy == [(0, 3), (5, 8), (20, 30)]
    assert tr.total(busy) == 16
    assert tr.clip(busy, (2, 25)) == [(2, 3), (5, 8), (20, 25)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]
    assert tr.gaps(busy, (0, 40)) == [(3, 5), (8, 20), (30, 40)]
    # idle share of that window: 24 of 40
    assert tr.total(tr.gaps(busy, (0, 40))) / 40 == 0.6


def test_gap_attribution_goes_to_the_innermost_span():
    idle = [(0, 10), (20, 24)]
    spans = [("bench/dispatch", 2, 30), ("PjitFunction(step)", 4, 8),
             ("bench/feed_wait", 21, 22)]
    assert tr.attribute(idle, spans) == {
        "unattributed": 2,            # 0-2
        "bench/dispatch": 2 + 2 + 3,  # 2-4, 8-10, 20-21 and 22-24
        "PjitFunction(step)": 4,      # 4-8, inside dispatch
        "bench/feed_wait": 1}
    assert tr.attribute([(0, 5)], []) == {"unattributed": 5}


def test_op_names_parse_to_instruction_opcode_shape():
    text = ("%fusion.12 = bf16[32768,3072]{1,0:T(8,128)(2,1)} fusion("
            "bf16[32768,768]{1,0} %p), kind=kOutput, calls=%fused.3")
    assert tr.parse_op_name(text) == (
        "fusion.12", "fusion", "bf16[32768,3072]{1,0:T(8,128)(2,1)}")
    instr, opcode, shape = tr.parse_op_name(
        "%all-reduce.1 = (f32[768,768]{1,0:T(8,128)S(1)}, f32[768,768]{1,0"
        ":T(8,128)S(1)}, f32[768,3072]{1,0}) all-reduce(f32[768,768] %a)")
    assert (instr, opcode) == ("all-reduce.1", "all-reduce")
    assert shape.endswith("...") and len(shape) == 48
    assert tr.parse_op_name("not hlo") == ("not hlo", "", "")
    assert tr.is_collective("all-reduce-start")
    assert tr.is_collective("reduce-scatter")
    assert not tr.is_collective("fusion") and not tr.is_collective("reduce")


def test_collective_time_and_the_part_nothing_else_covers():
    def op(name, opcode, s, e):
        return (f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %x)", s, e)

    dev = {"modules": [("jit_step(1)", 0, 100), ("jit_step(1)", 200, 300)],
           "ops": [op("fusion.1", "fusion", 0, 40),
                   op("all-reduce.1", "all-reduce", 30, 60),
                   op("while.1", "while", 0, 1000),     # encloses, skipped
                   op("fusion.2", "fusion", 50, 55),
                   op("all-reduce.2", "all-reduce", 200, 220)]}
    got = tr.reduce_device(dev, (0, 400))
    assert got["busy"] == [(0, 60), (200, 220)] and got["busy_ns"] == 80
    assert got["collective_ns"] == 50
    # exposed: 40-50 and 55-60 of the first, all of the second
    assert got["collective_exposed_ns"] == 10 + 5 + 20
    assert len(got["modules"]) == 2
    assert got["by_op"]["fusion.1 fusion f32[8]{0}"] == 40
    assert not any("while" in k for k in got["by_op"])
    # a window clips events and drops launches that start outside it
    cut = tr.reduce_device(dev, (35, 150))
    assert cut["busy_ns"] == 25 and cut["modules"] == []


@pytest.fixture(scope="module")
def recorded():
    return tr.summarize(os.path.join(DATA, "tiny_train_v5e.xplane.pb"))


def test_recorded_v5e_trace_planes_modules_and_busy(recorded):
    s = recorded
    assert dict(s["modules"]) == {"jit_convert_element_type": 5,
                                  "jit_step": 3, "jit_multi": 2} or \
        sum(n for _, n in s["modules"]) == s["launches"]
    assert {"jit_step", "jit_multi"} <= {n for n, _ in s["modules"]}
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["busy_s_per_device"] == [s["busy_s"]]
    assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
    # three 0.2 ms steps and two 0.85 ms scans of a tiny model (by hand)
    assert 0.0012 < s["busy_s"] < 0.0025
    assert s["collective_s"] == 0.0 and s["collective_exposed_s"] == 0.0
    top = [name for name, _ in s["top_ops"][:10]]
    assert all(" fusion " in n or " copy" in n or " reshape " in n
               for n in top), top
    assert not any(n.split()[1] in tr.CONTROL_FLOW for n, _ in s["top_ops"])
    # busy time is the union, never more than the sum of the ops
    assert s["busy_s"] <= sum(t for _, t in s["top_ops"]) * 1.0001


def test_recorded_v5e_trace_attributes_idle_gaps_to_host_spans(recorded):
    gaps = dict(recorded["idle_gaps"])
    assert {"bench/feed", "bench/dispatch"} <= set(gaps)
    assert any(k.startswith("PjitFunction(") for k in gaps)
    idle = recorded["idle_share"] * recorded["window_s"]
    assert sum(gaps.values()) <= idle * 1.0001
    assert sum(gaps.values()) > 0.9 * idle    # short gaps are left out


def test_recorded_four_chip_trace_finds_the_all_reduces():
    s = tr.summarize(os.path.join(DATA, "tiny_dp4_v5e.xplane.pb.gz"),
                     n_devices=4)
    assert len(s["busy_s_per_device"]) == 4
    assert s["busy_s"] == pytest.approx(sum(s["busy_s_per_device"]) / 4)
    assert max(s["busy_s_per_device"]) < 1.05 * min(s["busy_s_per_device"])
    # the window is the bench/slice annotation: three steps, and on chip 0
    # the seed's convert_element_type before each
    assert dict(s["modules"]) == {"jit_convert_element_type": 3,
                                  "jit_step": 3}
    assert s["launches"] == 6
    # one combined gradient all-reduce a step, the slowest op of a tiny
    # model, and with nothing else running beside it
    assert s["top_ops"][0][0].startswith("all-reduce all-reduce ")
    assert s["collective_s"] == pytest.approx(s["top_ops"][0][1])
    assert 0 < s["collective_exposed_s"] <= s["collective_s"]
    assert 0.1 < s["collective_s"] / s["busy_s"] < 0.5
    assert {"bench/dispatch", "bench/feed_wait"} <= set(dict(s["idle_gaps"]))
    one = tr.summarize(os.path.join(DATA, "tiny_dp4_v5e.xplane.pb.gz"))
    assert one["busy_s_per_device"] == s["busy_s_per_device"][:1]


def test_a_trace_with_no_tpu_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    with pytest.raises(ValueError, match="no /device:TPU"):
        tr.summarize(paths[0])
