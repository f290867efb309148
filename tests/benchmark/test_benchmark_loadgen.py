"""The traffic generator: the same seed gives the same inputs, another
seed differs, and the variance reductions keep the distributions."""
import json
import os

import numpy as np
import pytest

from benchmark import loadgen

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def _signature(reqs):
    return [(r.due_s, r.max_new, r.prompt.tolist()) for r in reqs]


def test_same_seed_same_schedule_other_seed_differs():
    mix = _mix("chat_open")
    a = loadgen.open_loop_requests(mix, 50257, 5, 2.0, 30.0)
    b = loadgen.open_loop_requests(mix, 50257, 5, 2.0, 30.0)
    c = loadgen.open_loop_requests(mix, 50257, 6, 2.0, 30.0)
    assert _signature(a) == _signature(b)
    assert _signature(a) != _signature(c)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert all(0.0 <= r.due_s < 30.0 for r in a)


def test_chat_mix_stays_inside_its_limits():
    mix = _mix("chat_open")
    reqs = loadgen.open_loop_requests(mix, 50257, 1, 8.0, 50.0)
    assert len(reqs) == 400            # fixed count: round(rate * horizon)
    p = np.array([len(r.prompt) for r in reqs])
    n = np.array([r.max_new for r in reqs])
    assert p.min() >= 8 and p.max() <= 192
    assert n.min() >= 4 and 32 < n.max() <= 64     # the issue's clip
    assert 20 <= np.median(n) <= 28    # the mix's median 24
    assert (p + n).max() <= mix["max_total_tokens"]
    assert 40 <= np.median(p) <= 56    # the mix's median 48
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50256 for r in reqs)


def test_stratified_lengths_keep_block_totals_steady():
    spec = {"dist": "lognormal", "median": 400, "sigma": 0.6, "min": 128,
            "max": 1000}
    plain = loadgen.draw_lengths(np.random.default_rng(0), 800, spec)
    strat = loadgen.draw_lengths(np.random.default_rng(0), 800,
                                 dict(spec, stratify=8))
    assert abs(plain.mean() - strat.mean()) < 0.05 * plain.mean()
    blocks = strat.reshape(100, 8).mean(1)
    assert blocks.std() < 0.5 * plain.reshape(100, 8).mean(1).std()
    assert loadgen.draw_lengths(np.random.default_rng(0), 3,
                                {"dist": "fixed", "value": 7}).tolist() == \
        [7, 7, 7]
    with pytest.raises(ValueError):
        loadgen.draw_lengths(np.random.default_rng(0), 3, {"dist": "zipf"})


def test_arrivals_are_poisson_conditioned_on_their_count():
    t = loadgen.arrival_times(np.random.default_rng(1), 5.0, 200.0)
    assert len(t) == 1000 and (np.diff(t) > 0).all()
    assert 0.0 < t.min() and t.max() < 200.0
    gaps = np.diff(t)
    assert 0.85 < gaps.std() / gaps.mean() < 1.15      # exponential gaps
    # the same load whatever the seed; only its timing varies
    other = loadgen.arrival_times(np.random.default_rng(2), 5.0, 200.0)
    assert len(other) == 1000 and (other != t).any()
    assert len(loadgen.arrival_times(np.random.default_rng(1), 0.2,
                                     51.0)) == 10
    with pytest.raises(ValueError):
        loadgen.arrival_times(np.random.default_rng(1), 0.0, 10.0)


def test_closed_loop_callers_share_one_seeded_stream():
    mix = _mix("score_short")
    take = lambda g, n: [next(g) for _ in range(n)]     # noqa: E731
    a = take(loadgen.closed_loop_requests(mix, 50257, 3), 70)
    again = take(loadgen.closed_loop_requests(mix, 50257, 3), 70)
    other = take(loadgen.closed_loop_requests(mix, 50257, 4), 70)
    assert [r.prompt.tolist() for r in a] == \
        [r.prompt.tolist() for r in again]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in other]
    assert all(r.max_new == 1 and 32 <= len(r.prompt) <= 256 for r in a)
    assert [r.index for r in a] == list(range(70))
    # stratified in blocks of 8: whole blocks carry nearly equal work
    sums = np.array([len(r.prompt) for r in a[:64]]).reshape(8, 8).sum(1)
    assert sums.std() / sums.mean() < 0.06


@pytest.mark.parametrize("name", ["chat_open", "score_short",
                                  "score_long"])
def test_the_seed_makes_every_serving_mix(name):
    """`--seed` makes the traffic: lengths, tokens and (open loop) due
    times all change with it, in every mix the benchmark has."""
    mix = _mix(name)
    assert "pinned_schedule_seed" not in mix
    if mix["driver"] == "serve_open":
        draw = lambda s: loadgen.open_loop_requests(    # noqa: E731
            mix, 50257, s, 0.2, 51.0)
    else:
        draw = lambda s: [r for r, _ in zip(            # noqa: E731
            loadgen.closed_loop_requests(mix, 50257, s), range(40))]
    a, b = draw(1), draw(2)
    assert len(a) == len(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.prompt[:8].tolist() for r in a] != \
        [r.prompt[:8].tolist() for r in b]
    if mix["driver"] == "serve_open":
        assert [r.due_s for r in a] != [r.due_s for r in b]
        assert [r.max_new for r in a] != [r.max_new for r in b]


def test_score_long_is_the_issues_mix_and_score_short_its_short_end():
    long, short = _mix("score_long"), _mix("score_short")
    assert (long["prompt_tokens"]["min"], long["prompt_tokens"]["median"],
            long["prompt_tokens"]["max"]) == (128, 400, 1000)
    assert short["prompt_tokens"]["max"] <= 256 and "cut_from" in short
    assert long["callers"] == short["callers"] == 4
    take = lambda m: [len(r.prompt) for r, _ in zip(    # noqa: E731
        loadgen.closed_loop_requests(m, 50257, 1), range(256))]
    assert 360 <= np.median(take(long)) <= 440
    assert max(take(long)) > 512       # the 1,024 bucket is reached


def test_training_batches_are_seeded_zipf_and_learnable():
    mix = {"seq_len": 16, "steps_per_dispatch": 4, "zipf_exponent": 1.0}
    a = next(loadgen.training_batches(mix, 1000, 9, 8))
    b = next(loadgen.training_batches(mix, 1000, 9, 8))
    c = next(loadgen.training_batches(mix, 1000, 10, 8))
    assert a["ids"].shape == (4, 8, 16) and a["labels"].shape == (4, 8, 16, 1)
    assert a["ids"].dtype == np.int32
    assert (a["ids"] == b["ids"]).all() and (a["ids"] != c["ids"]).any()
    assert (a["labels"][..., 0] == a["ids"]).all()
    assert (a["pos"][0, 0] == np.arange(16)).all()
    per_step = next(loadgen.training_batches(
        dict(mix, steps_per_dispatch=1), 1000, 9, 8))
    assert per_step["ids"].shape == (8, 16)
    # Zipf: the commonest token is far commoner than a uniform draw's
    big = next(loadgen.training_batches(
        {"seq_len": 512, "steps_per_dispatch": 1, "zipf_exponent": 1.0},
        1000, 1, 64))
    assert (big["ids"] == 0).mean() > 0.05
    assert 0 <= big["ids"].min() and big["ids"].max() < 1000
