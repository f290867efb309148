"""What `serving_moe_hybrid.py` does for nemotron, for the `cohere2_moe`
decoder (window and full attention layers over a device-only KV cache with
a ring, a parallel block, gated routed experts beside averaged shared
experts), one chip's share of it: build it through the public entry points
and compare what it served with its plain reference — the comparison that
decides `correct`.  What is generic is imported (`serving.Served`'s `post`
/ `close`, `serving.delta` / `check_replies` / `next_pow2`,
`serving_cached.Sampler`, `serving_moe_hybrid.moe_counters` / `summary`);
what is nemotron's by name there (`Served`'s construction, the reference,
its limits, the warm-up's count of decode buckets, `conclude`) has a twin
here.
"""
import tempfile

import numpy as np

from benchmark import serving, serving_cached, serving_moe_hybrid
from benchmark.reference import cohere2_moe as reference

# `serving_moe_hybrid`'s rule, its four limits re-read for 8 picks of 128
# (the same sigmoid gate, the same discontinuity): the served gate's picks
# are read back after the window (`served_picks`) and the reference is
# GIVEN them, so its residual follows the served one and every layer's
# gate is compared on the same layer input; the picks are held to
# PICK_EPSILON (the largest shortfall of a served pick under the
# reference's own 8th-best score) and PICKS_APART (the share of (layer,
# token) rows whose sets differ in more than one expert), the served
# tokens' margins to MEAN_SIGMA and TIE_SIGMA.  TWO MORE are this model's
# own, because the margins of served tokens cannot see a window or a ring:
# with seeded weights a query spreads its attention almost evenly, and
# whether some keys are averaged in or not moves a logit by less than it
# takes to change an argmax.  On the sampled sequences that PASS THE
# WINDOW (at least LONG of them, more context than `sliding_window`):
# - HIDDEN_APART: the residual stream after the FIRST sliding layer at the
#   sequence's last row, on the served path (`model.hidden_row`: the ops
#   `prefill_step` runs, compiled by this check) against the reference's,
#   |h_served - h_ref| / |h_ref - embedding row| (the error as a share of
#   what the layer added), the largest over those sequences: THE PROMPT
#   PATH (rotary, the prompt kernel under its window mask);
# - DECODE_APART: the sequence REPLAYED, teacher-forced, through the
#   ENGINE'S OWN executables over the pool's own arrays
#   (`served_decode_logits`: the warm-up's prefill programs with
#   `kv_ring_pack`, the one decode program with `cached_decode_attention`
#   writing and reading the ring across its wraps, the sampled sequences
#   side by side) and the decode program's logits compared with the
#   reference's, |l_served - l_ref| / |l_ref - its mean| a row, a
#   sequence's MEDIAN row, the largest over those sequences: THE DECODE
#   PATH, which nothing else here reaches.
#
# Controls, each of which has to come out as NOT correct
# (`benchmark/tools/cohere2_limit_readings.py` runs all of them through
# `check_against_reference` and `within_limits`): the reference's matrices
# rounded through int8 (the nearest precision below the bfloat16 the
# configuration states); the reference with the window mask taken off the
# sliding layers; the reference without rotary positions; and the
# reference with the ring kept wrongly from the prompt's end on
# (`reference.RING_FAULTS`: "stale", the decode step's new column never
# written — a decoded row misses t + 1 of 4,096 columns at step t, the
# subtlest fault of a ring there is — and "unwrapped", the valid columns
# miscounted past a wrap).
# Readings (v5e, the published widths; samples of 12 served sequences of
# which 5-9 pass the window = 1,000-1,900 served tokens and 181,000-
# 270,000 (layer, token) rows; PERF.md section 6 has them by call), sound
# | int8 | window mask off | no rotary | ring stale | ring unwrapped
# (sound: the tool on six seeds and the cell's eighteen runs; int8, window,
# rotary: the tool on four seeds; stale on four, unwrapped on two):
# - a sequence past the window's median decoded row (sound: the seven
#   readings since the replay exists, 45 sequences): 0.00446-0.00550
#   (bfloat16 rounding through four layers and the head) | 0.01519-0.01641
#   | 0.027-0.379 | 0.082-0.105 | 0.0245-0.109 | 0.029-0.69: EVERY
#   CONTROL FAILS BY IT.  DECODE_APART = 0.009 stands 1.64x over the
#   largest sound reading and 1.69x under the smallest of int8 (2.7x
#   under the smallest sequence of a stale ring; a sample's largest, which
#   is what is held to it, reads 0.086-0.109 there, 10x over);
# - the first sliding layer's last row, each sequence past the window:
#   0.00424-0.00478 (150 sequences, the same whatever the seed) |
#   0.00786-0.00850 (24) | 0.018-0.122 | 0.106-0.184 | 0.055-0.31 |
#   0.024-0.85 (a ring's fault reaches it where the last row was decoded):
#   every control fails by it too.  HIDDEN_APART = 0.0062 stands 1.30x
#   over the largest sound reading and 1.27x under the smallest of int8:
#   both sides spread by +-6% and +-4% of their means (rounding of a
#   4,096-wide row averages out), so 1.3x is some twenty of their standard
#   deviations;
# - rows whose sets differ by more than one expert 1.0e-05-6.0e-05 (2-13
#   rows, a Poisson count of mean 7) | 1.5e-04-2.0e-04 | 0.032-0.052 |
#   0.21-0.22 | 0.0071-0.0087 | 0.0062-0.011: PICKS_APART = 1e-04 = ~21
#   rows, 1.7x over the largest sound reading and 1.5x under the smallest
#   of int8 (rows that differ at all, a count of thousands: 3.7-4.1% |
#   6.9-7.2% | 10.9-15.4% | 59-60%; the sigmoid's top 8 of 128 tie less
#   than nemotron's top 22 of 512);
# - largest shortfall 0.0041-0.0065 | 0.0078-0.0148 | 0.39-0.65 |
#   0.32-0.47 | 0.36-0.61 | 0.63-0.79: a maximum over ~200,000 rows, so
#   PICK_EPSILON = 0.02 (3.1x the largest sound reading) guards gross
#   faults, which read 0.3 and more;
# - served-token margins, worst: 0-0.158 (23 readings: three over 0.1,
#   eight under 0.001; a token whose decode-time gate picked another
#   expert than `served_picks` reads back) | 0.024-0.053 | 0.61-1.54 |
#   0.29-0.36 | 0.11-0.29 | 1.2-2.8; mean: 0-0.00021 | 0.00002-0.00015 |
#   0.00056-0.032 | 0.00046-0.0069 | 0.00037-0.0031 | 0.12-0.23.  With the
#   picks given, most served tokens ARE the reference's argmax (at
#   granite's table std 0.05 every margin read exactly 0: the tied head
#   read the last token's own row back 6 sigmas up; the configuration's
#   `embed_init_rms` 0.0125 puts it under the row's maximum), so the
#   margins do not separate int8 and are not asked to.  TIE_SIGMA = 0.5
#   lies between the largest sound reading (3.2x over it) and the
#   smallest of the window control (1.2x under), MEAN_SIGMA = 0.0004
#   between 0.00021 (1.9x) and the smallest of the window and rotary
#   controls (1.15x): the room is put on the sound side on purpose — both
#   are extremes of a few heavy-tailed tokens among 1,500 (one token at
#   0.5 sigma is a mean of 0.00033), a run that reads past them refuses an
#   innocent PR, and every control is held by DECODE_APART and
#   HIDDEN_APART whatever its margins read.
TIE_SIGMA = 0.5
MEAN_SIGMA = 0.0004
PICK_EPSILON = 0.02
PICKS_APART = 1e-4
HIDDEN_APART = 0.0062
DECODE_APART = 0.009
SAMPLE = 12
LONG = 3


def model_config(cfg, eng):
    """`Cohere2MoeConfig` for the share the configuration file states: the
    router at its published width, `num_experts` experts held from
    `first_held_expert` on, the first `vocab_size` rows of the vocabulary,
    the first `num_hidden_layers` of `layer_types`."""
    from paddle_tpu.models import Cohere2MoeConfig
    published = dict(cfg, num_experts=cfg["published"]["num_experts"])
    return Cohere2MoeConfig.from_published(
        published, held_experts=cfg["num_experts"],
        first_held=cfg["first_held_expert"], vocab_rows=cfg["vocab_size"],
        layers=(0, cfg["num_hidden_layers"]),
        max_position=cfg["max_position_embeddings"],
        eos_id=cfg["eos_token_id"], bos_id=cfg["eos_token_id"],
        dtype=eng["dtype"], embed_init_rms=cfg.get("embed_init_rms", 0.05))


class Served(serving.Served):
    """The system under test: the decoder behind `InferenceServer` (`post`
    and `close` are `serving.Served`'s)."""

    def __init__(self, run):
        import paddle_tpu
        import paddle_tpu.static as static
        from paddle_tpu.inference.server import InferenceServer
        from paddle_tpu.models import Cohere2MoeModel

        cfg, eng = run.config, run.config["engine"]
        self.cfg = dict(cfg, n_positions=eng["max_context"])
        paddle_tpu.seed(run.seed)            # the weights come from --seed
        self.model = Cohere2MoeModel(model_config(cfg, eng))
        self.plan = static.page_budget(
            self.model, page_tokens=eng["page_tokens"],
            max_context=eng["max_context"], hbm_bytes=eng["hbm_bytes"],
            max_slots_cap=eng["max_slots_cap"])
        run.log("plan: " + ", ".join(f"{k}={self.plan[k]}" for k in (
            "pages", "max_slots", "max_context", "kv_bytes", "kv_slot_bytes",
            "weight_bytes", "state_slot_bytes", "workspace_bytes")))
        self._dir = tempfile.TemporaryDirectory()
        serving._save_stub_predictor(self._dir.name)
        self.server = InferenceServer(self._dir.name, generator=self.model,
                                      gen_kv_pool=self.plan)
        self.server.start()
        self.max_slots = int(self.plan["max_slots"])

    def reference_params(self):
        return reference.params_of(self.model)


Sampler = serving_cached.Sampler
moe_counters = serving_moe_hybrid.moe_counters


def warm_up(served, run):
    """`serving.warm_up` for an engine whose decode step is ONE program
    (it is given the KV arrays whole, whatever its rows hold): one request
    a reachable prefill bucket, three tokens each so that the decode
    program runs too, and a check that the engine saw exactly those."""
    from paddle_tpu.serving.metrics import serving_stats
    mix = run.traffic
    p_lo, p_hi = serving._length_range(mix["prompt_tokens"])
    prefill, _ = serving.reachable_buckets(mix, served.cfg["n_positions"])
    rng = np.random.default_rng([run.seed, 99])
    for b in prefill:
        p = min(max(p_lo, b // 2 + 1), p_hi)
        served.post(rng.integers(0, served.cfg["vocab_size"] - 1, p), 3,
                    timeout_s=3600.0)
    want = len(prefill) + 1
    got = int(serving_stats().get("serving.gen.kv_buckets", 0))
    run.log(f"warm-up: {len(prefill)} requests over prefill buckets "
            f"{prefill} and the one decode program; engine reports {got} "
            "buckets")
    if got != want:
        raise RuntimeError(
            f"warm-up touched {got} engine buckets, the mix reaches {want}")


def conclude(run, served, sampler, last, done, moe_first):
    """`serving_moe_hybrid.conclude` for this model: counters over the
    window (the routed experts' among them), the engine's forwards inside
    the traced slice, and the checks that decide `correct`.  `done`:
    (request, tokens) of every reply."""
    run.samples["kv_pages_used_share"] = sampler.page_samples
    run.samples["state_slots_used_share"] = sampler.state_samples
    moe_now = moe_counters()
    moe = {k: moe_now[k] - moe_first[k] for k in moe_now}
    run.counters.update(serving.delta(last, sampler.first),
                        max_slots=served.max_slots,
                        **{"moe." + k: v for k, v in moe.items()})
    run.log(f"counters over the window: {run.counters}; "
            f"{last['queue_depth']} queued at its end")
    held = run.config["num_experts"]
    if moe["expert_steps"] and moe["pairs_routed"]:
        run.log(f"experts over the window: {moe['pairs_held']} of "
                f"{moe['pairs_routed']} routed pairs landed on the {held} "
                f"held ({100.0 * moe['pairs_held'] / moe['pairs_routed']:.2f}"
                f"%), {moe['experts_touched'] / moe['expert_steps']:.2f} "
                "experts touched a layer a call")
    edges = sampler.at_edge
    if "start" in edges and "stop" in edges:
        d = serving.delta(edges["stop"], edges["start"])
        prefills = max(0, d["gen.admitted"] - (
            edges["stop"]["queue_depth"] - edges["start"]["queue_depth"]))
        run.slice_units = (d["gen.steps"] + prefills) or None
        run.log(f"slice: {d['gen.steps']} decode steps, {prefills} "
                f"prefills, {d['gen.tokens']} decoded rows")
    got = check_against_reference(served, done, run.seed)
    if got is not None:
        run.log(f"router: served top-{served.cfg['num_experts_per_tok']} "
                "sets against the reference's on the same layer input, "
                f"{got['rows']} (layer, token) rows of the sample: largest "
                f"shortfall {got['shortfall']} (limit {PICK_EPSILON}), "
                f"{got['apart']} of the rows differ by more than one expert "
                f"(limit {PICKS_APART}) and {got['differ']} at all; by "
                f"layer {got['by_layer']}")
        run.log(f"reference: served-token margins over a sample of "
                f"{got['sequences']} ({got['long']} past the window): worst "
                f"{got['worst']} sigma (limit {TIE_SIGMA}), mean "
                f"{got['mean']} sigma (limit {MEAN_SIGMA}); the first "
                "sliding layer's last row on the sequences past the "
                f"window: {got['hidden_each']} of what the layer added "
                f"(limit {HIDDEN_APART}); the engine's own prefill and "
                "decode programs replayed over the pool's arrays, logits "
                "against the reference's, a sequence past the window's "
                f"median decoded row: {got['decode_each']} of the row's "
                f"spread (limit {DECODE_APART}; inside the window at most "
                f"{got['decode_short']}, a prefill's row at most "
                f"{got['prefill_row']})")
    run.checks.update(
        replies_well_formed=serving.check_replies(served, done),
        matches_reference=got is not None and within_limits(got))
    run.correct = bool(done)


def _static(served, fn):
    from paddle_tpu.jit import StaticFunction
    return StaticFunction(fn, layer=served.model, abstract_trace=True)


def _padded(served, toks):
    """(ids [1, width], lengths [1], last [1]) tensors of a sequence padded
    to the engine's longest context (one compiled shape for all)."""
    import paddle_tpu
    n = len(toks)
    padded = np.zeros((1, served.cfg["n_positions"]), np.int32)
    padded[0, :n] = toks
    return (paddle_tpu.to_tensor(padded),
            paddle_tpu.to_tensor(np.asarray([n], np.int32)),
            paddle_tpu.to_tensor(np.asarray([n - 1], np.int32)))


def served_picks(served, done):
    """The experts the SERVED gate picks for every token of the sequences
    of `done`, a sequence [layers, T, k]: the model's own `routes` (the
    layers and ops `prefill_step` runs, bfloat16 activations into the
    float32 gate) compiled once at the engine's longest context."""
    from paddle_tpu.dygraph.base import no_grad
    routes, out = _static(served, served.model.routes), []
    for _, toks in done:
        ids, lengths, _ = _padded(served, toks)
        with no_grad():
            got = routes(ids, lengths)
        out.append(np.asarray(got.numpy())[:, 0, :len(toks)])
    return out


def served_hidden(served, done, upto=1):
    """The served path's residual stream after the first `upto` layers at
    each sequence's last row, float32 [hidden] a sequence
    (`model.hidden_row`, compiled once at the longest context)."""
    from paddle_tpu.dygraph.base import no_grad

    def first_layers(ids, lengths, last):
        return served.model.hidden_row(ids, lengths, last, upto)

    row = _static(served, first_layers)
    out = []
    for _, toks in done:
        with no_grad():
            got = row(*_padded(served, toks))
        out.append(np.asarray(got.numpy(), np.float32)[0])
    return out


def served_decode_logits(served, done):
    """What the ENGINE'S OWN compiled programs and device arrays give for
    the sequences of `done`, replayed after the window while the engine is
    idle, `max_slots` of them at a time: each prompt through the prefill
    program of its bucket (`engine.step_programs`, the executables of the
    warm-up: `kv_ring_pack` leaves the ring as it stands after a prompt
    longer than the window) and into a state slot of the pool's arrays
    (`kv_pool.state`), then all of them SIDE BY SIDE through the one decode
    program, each row given the token the engine served next at its own
    length until its answer ends: the new columns are written where the
    rings wrap and `cached_decode_attention` reads across the wraps, rows
    on both sides of the window in one launch, as in the window.  A
    sequence: float32 [answer tokens, vocabulary], row t the logits that
    answer token t was picked from (row 0 the prefill's, the rest the
    decode program's)."""
    import paddle_tpu
    engine = served.server.engine
    steps, state = engine.step_programs, engine.kv_pool.state
    slots, extra = engine.max_slots, len(steps.counters)

    def device(*arrays):
        return [paddle_tpu.to_tensor(a) for a in arrays]

    out = []
    for first in range(0, len(done), slots):
        batch = done[first:first + slots]
        rows = []
        for slot, (req, _) in enumerate(batch):
            p = len(req.prompt)
            ids = np.zeros((1, min(serving.next_pow2(p),
                                   served.cfg["n_positions"])), np.int32)
            ids[0, :p] = req.prompt
            logits, _, *made = steps.prefill(*device(
                ids, np.asarray([p], np.int32), np.asarray([p - 1], np.int32)))
            state.install(slot, **{n: t._value
                                   for n, t in zip(state.names, made)})
            rows.append([np.asarray(logits.numpy(), np.float32)[0]])
        for t in range(max(len(toks) - len(req.prompt)
                           for req, toks in batch) - 1):
            ids = np.zeros(slots + extra, np.int32)
            lengths = np.zeros(slots, np.int32)
            active = np.zeros(slots, np.int32)
            for slot, (req, toks) in enumerate(batch):
                at = len(req.prompt) + t
                if at < len(toks) - 1:  # the token served at `at`, its cache
                    ids[slot], lengths[slot], active[slot] = toks[at], at, 1
            logits, _, *new = steps.decode(*device(ids, lengths, active),
                                           *state.arrays.values())
            state.rebind(**{n: a._value for n, a in zip(state.names, new)})
            logits = np.asarray(logits.numpy(), np.float32)
            for slot in np.flatnonzero(active):
                rows[slot].append(logits[slot])
        out.extend(np.stack(r) for r in rows)
    return out


def readings(served, done, weights_as=None, forced=True, window=True,
             rotary=True, ring=None):
    """Each sequence of `done` teacher-forced through the plain reference,
    GIVEN the served gate's picks (`forced` False: left to its own, the
    margins only).  A sequence: {"margins", "shortfall" [layers, T],
    "apart" [layers, T]} as `serving_moe_hybrid.readings`; "decode"
    [answer tokens]: how far the engine's own programs' logits
    (`served_decode_logits`) lie from the reference's at each answer
    position, |l_served - l_ref| over |l_ref - its mean|; "past": whether
    the sequence passes the window; and, where it does, "hidden": |h_served
    - h_ref| / |h_ref - embedding row| after the first sliding layer at
    its last row.  `weights_as`, `window`, `rotary`, `ring` (from the
    prompt's end on): the controls, on the reference's side."""
    params, out = served.reference_params(), []
    given = served_picks(served, done) if forced else [None] * len(done)
    w = int(served.cfg["sliding_window"])
    first = list(served.cfg["layer_types"]).index("sliding_attention") + 1
    rows = served_hidden(served, done, first)
    replayed = served_decode_logits(served, done)
    for (req, toks), picks, h_served, l_served in zip(done, given, rows,
                                                      replayed):
        n_prompt, n = len(req.prompt), len(toks)
        width = serving.next_pow2(n)
        padded = np.zeros(width, np.int32)
        padded[:n] = toks
        own, short, hidden = [], [], []
        if picks is not None:           # pads route anywhere: causal layers
            picks = np.pad(picks, ((0, 0), (0, width - n), (0, 0)))
        logits = np.asarray(reference.logits(
            params, padded, served.cfg, weights_as=weights_as, picks=own,
            forced=picks, shortfall=short, window=window, rotary=rotary,
            rows=np.arange(n_prompt - 1, n - 1), hidden=hidden, ring=ring,
            decode_from=n_prompt))
        got = {"margins": np.asarray([
            float(row.max() - row[toks[n_prompt + t]]) / float(row.std())
            for t, row in enumerate(logits)]),
            "decode": np.linalg.norm(l_served - logits, axis=-1)
            / np.linalg.norm(logits - logits.mean(-1, keepdims=True),
                             axis=-1),
            "past": n > w}
        if n > w:
            h_ref = np.asarray(hidden[first - 1][n - 1], np.float32)
            added = h_ref - np.asarray(
                params["embed"][int(toks[n - 1])], np.float32)
            got["hidden"] = float(np.linalg.norm(h_served - h_ref)
                                  / np.linalg.norm(added))
        if picks is not None:
            got["shortfall"] = np.stack([np.asarray(x)[:n] for x in short])
            got["apart"] = np.asarray([
                [len(set(a) - set(b)) for a, b in zip(sa[:n], ra[:n])]
                for sa, ra in zip(picks, np.asarray(own))])
        out.append(got)
    return out


def summary(per_sequence):
    """`serving_moe_hybrid.summary` of `readings`, with "sequences", "long"
    (how many pass the window), "hidden_each" (theirs, rounded), "hidden"
    (the largest; None where none passes), "decode_each" (a sequence past
    the window: the MEDIAN of its decoded rows' distance, rounded — a row
    whose gate picked another expert at decode time than `served_picks`
    reads back stands a whole expert apart, a few rows in a hundred,
    which a median passes over and a fault of the ring, in every row,
    does not), "decode" (the largest), "decode_short" (the largest of
    the sequences inside the window) and "prefill_row" (the largest
    distance of a prefill's row, all sequences)."""
    got = serving_moe_hybrid.summary(per_sequence)
    each = [r["hidden"] for r in per_sequence if "hidden" in r]
    medians = [(r["past"], float(np.median(r["decode"][1:])))
               for r in per_sequence if len(r["decode"]) > 1]
    past = [m for long, m in medians if long]
    short = [m for long, m in medians if not long]
    got.update(sequences=len(per_sequence), long=len(each),
               hidden_each=[round(x, 5) for x in each],
               hidden=max(each) if each else None,
               decode_each=[round(x, 5) for x in past],
               decode=max(past) if past else None,
               decode_short=max(short) if short else None,
               prefill_row=max(float(r["decode"][0]) for r in per_sequence))
    return got


def within_limits(got):
    """Whether `summary`'s readings (picks given) pass all six limits, on
    a sample of which at least LONG sequences pass the window."""
    return bool(got["worst"] <= TIE_SIGMA and got["mean"] <= MEAN_SIGMA
                and got["shortfall"] <= PICK_EPSILON
                and got["apart"] <= PICKS_APART
                and got["long"] >= LONG and got["hidden"] <= HIDDEN_APART
                and got["decode"] <= DECODE_APART)


def sample_of(done, seed, sample, window, long=LONG):
    """A seeded sample of `sample` served sequences of which at least
    `long` (as far as there are so many) pass `window` tokens of context:
    a seeded draw, its last entries swapped for long sequences where it
    drew too few."""
    rng = np.random.default_rng([seed, 7])
    chosen = [int(i) for i in rng.choice(
        len(done), size=min(sample, len(done)), replace=False)]
    is_long = [len(toks) > window for _, toks in done]
    spare = [i for i in rng.permutation(len(done))
             if is_long[i] and i not in chosen]
    at = len(chosen) - 1
    while sum(is_long[i] for i in chosen) < long and spare and at >= 0:
        if not is_long[chosen[at]]:
            chosen[at] = int(spare.pop())
        at -= 1
    return chosen


def check_against_reference(served, done, seed, sample=SAMPLE, keep=None,
                            **control):
    """`summary` of `readings` over `sample_of` the served sequences (what
    `within_limits` holds to the six limits); None where nothing was
    served.  `keep`: a list that receives the sample's `readings`;
    `control`: `weights_as` / `window` / `rotary` / `ring` for the
    reference."""
    if not done:
        return None
    chosen = sample_of(done, seed, sample, int(served.cfg["sliding_window"]))
    per_sequence = readings(served, [done[i] for i in chosen], **control)
    if keep is not None:
        keep.extend(per_sequence)
    return summary(per_sequence)
