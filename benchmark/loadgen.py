"""Seeded traffic: arrival schedules, request lengths, training batches.

One general generator reads a traffic mix (a data file under
`benchmark/traffic/`) and a seed, and produces everything the program is
given: the program only ever receives generated inputs.  The same seed
gives the same schedule, lengths and tokens; another seed differs.  A new
mix of these shapes is a new data file; a new shape of traffic (bursts,
shared prefixes) brings its lines here with the cell that needs it.
"""
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()

# independent streams of one seed: a change to one mix parameter must not
# re-draw everything else
_ARRIVALS, _PROMPT_LEN, _NEW_LEN, _TOKENS, _BATCHES = range(5)


def _rng(seed, stream, sub=0):
    return np.random.default_rng([int(seed), int(stream), int(sub)])


def draw_lengths(rng, n, spec):
    """`n` integer lengths from a length spec: {"dist": "fixed", "value"}
    or {"dist": "lognormal", "median", "sigma", "min", "max"} (clipped).
    With "stratify": k the draws come in blocks of k, one from each of k
    equally likely strata in shuffled order: the same distribution, but
    every k consecutive requests carry nearly the same total work, so a
    run's work does not swing with the seed."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    k = int(spec.get("stratify", 0))
    if k > 1:
        blocks = -(-n // k)
        u = np.concatenate([(rng.permutation(k) + rng.random(k)) / k
                            for _ in range(blocks)])[:n]
        z = np.asarray([_NORMAL.inv_cdf(float(x)) for x in u])
    else:
        z = rng.standard_normal(n)
    raw = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(rng, rate_per_s, horizon_s):
    """Due times in [0, horizon), ascending: a Poisson process conditioned
    on its expected number of arrivals, round(rate * horizon) — exponential
    gaps scaled to fill the horizon — so every run offers the same load
    and only its timing varies with the seed."""
    if rate_per_s <= 0:
        raise ValueError(f"arrival rate must be > 0, got {rate_per_s}")
    n = int(round(rate_per_s * horizon_s))
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * horizon_s


class Request:
    """One generated request: when it is due (open loop; None in a closed
    loop), its prompt and how many tokens it asks for."""
    __slots__ = ("index", "due_s", "prompt", "max_new")

    def __init__(self, index, due_s, prompt, max_new):
        self.index, self.due_s = index, due_s
        self.prompt, self.max_new = prompt, int(max_new)


def _requests(mix, vocab_size, seed, n, sub=0):
    """`n` requests' prompts and lengths (no due times).  Token ids avoid
    the last id, which the served GPT-2 configurations use as EOS."""
    prompt_len = draw_lengths(_rng(seed, _PROMPT_LEN, sub), n,
                              mix["prompt_tokens"])
    new_len = draw_lengths(_rng(seed, _NEW_LEN, sub), n, mix["new_tokens"])
    cap = mix.get("max_total_tokens")
    if cap is not None:
        new_len = np.maximum(1, np.minimum(new_len, cap - prompt_len))
    tok = _rng(seed, _TOKENS, sub)
    return [Request(i, None,
                    tok.integers(0, vocab_size - 1,
                                 int(prompt_len[i])).astype(np.int64),
                    new_len[i]) for i in range(n)]


def open_loop_requests(mix, vocab_size, seed, rate_per_s, horizon_s):
    """The whole open-loop schedule for one run, due times ascending."""
    due = arrival_times(_rng(seed, _ARRIVALS), rate_per_s, horizon_s)
    reqs = _requests(mix, vocab_size, seed, len(due))
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


def closed_loop_requests(mix, vocab_size, seed, block=64):
    """The endless stream a closed loop's callers share: each takes the
    next request when its last one is answered.  One stream, not one a
    caller, so that stratified lengths fill whole strata blocks whichever
    caller is faster."""
    index = 0
    while True:
        for r in _requests(mix, vocab_size, seed, block, 1 + index // block):
            r.index = index
            index += 1
            yield r


def zipf_probabilities(vocab_size, exponent):
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -float(exponent)
    return p / p.sum()


def training_batches(mix, vocab_size, seed, global_batch):
    """An endless stream of feed dicts for the BERT pretraining program:
    `ids` drawn Zipf-distributed over the vocabulary (natural text is; a
    uniform draw would show every token about once a step and teach
    nothing inside a run), `pos` the positions, `labels = ids` so the task
    is learnable and the loss check means something.  With
    `steps_per_dispatch` K > 1 every array carries a leading K axis (the
    shape `Executor.run_steps` takes)."""
    seq, k = int(mix["seq_len"]), int(mix["steps_per_dispatch"])
    cdf = np.cumsum(zipf_probabilities(vocab_size, mix["zipf_exponent"]))
    lead = (k,) if k > 1 else ()
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32),
                          lead + (global_batch, seq)).copy()
    rng = _rng(seed, _BATCHES)
    while True:
        u = rng.random(lead + (global_batch, seq))
        ids = np.minimum(np.searchsorted(cdf, u), vocab_size - 1).astype(
            np.int32)
        yield {"ids": ids, "pos": pos, "labels": ids[..., None]}
