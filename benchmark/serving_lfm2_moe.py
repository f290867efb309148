"""What `serving_pangu_ultra_moe.py` does for openPangu, for the `lfm2_moe`
decoder (gated short-convolution layers with their tails in a state group,
QK-normed half-split-rotary attention over a device-only KV cache, every
expert of a layer held at top-4, no shared expert): build it through the
public entry points and compare what it served with its plain reference —
the comparison that decides `correct`.  What is generic is imported
(`serving.Served`'s `post` / `close`, `serving.warm_up` — this engine's
decode step is a program a power-of-two bound on the columns, as
granite's — `serving.delta` / `check_replies` / `next_pow2`,
`serving_cached.Sampler`, `serving_moe_hybrid.moe_counters`,
`serving_cohere2_moe.sample_of`); what is this model's by name (`Served`'s
construction and weights, the reference, its limits, the replay,
`conclude`) is here.

THE CHECK.  A seeded sample of the served sequences (`SAMPLE`, at least
`LONG` of them with more than `LONG_TOKENS` tokens where the window served
so many) is REPLAYED, teacher-forced, through the ENGINE'S OWN programs
over the pool's own arrays (`served_decode_logits`: each prompt through
the prefill program of its bucket — the blocked prompt kernel past 512
tokens, the short convolutions from empty tails — and into a slot of the
KV and tail arrays, then all of them side by side through the decode
program of the bound the engine takes, `next_pow2` of the longest row —
the conv tails carried across the prompt / decode boundary and from step to
step, `cached_decode_attention` over the KV columns).  The prefill's row
and those of the first `DECODED` decode steps of each sequence are
compared with the plain reference's full forward over the same tokens,
|l_served - l_ref| / |l_ref - its mean|, and every token the window served
at those rows is held to the reference's row: its margin under the row's
maximum, in the row's standard deviations.

WHY A SECOND REPLAY, AND ITS PICKS.  The reference is GIVEN the experts the
served programs picked for each token where they computed it (the
prefill's for the prompt, each decode step's for its token).  With 32
experts, top 4 and seeded weights, bfloat16 rounding moves the 4th and 5th
scores past each other in ~5% of a layer's rows, and a token routed
otherwise reaches every later token through attention, so two runs route
ever more rows apart layer after layer (the float32 reference left to its
own picks chose otherwise in 4-7% of the rows of the first expert layer
and 82-85% of the tenth, v5e; PERF.md section 6): only the picks of the
very program whose logits are compared say what the reference has to
compute.  The engine's programs do not return them (that would change
every expert model's programs), so the sample is replayed once more
through their twins — the same step functions at the same buckets and
bound, wrapped as `StepPrograms` wraps them, with the picks as one result
more (`prefill_step` / `decode_step(picks=True)`) — and the twins' logits
are held to the engine programs' (TWIN_APART: the same arithmetic, to the
bit) before their picks are used.  The picks' gaps under the reference's
own 4th-best biased score are held to PICK_EPSILON and their sets, where
they differ from the reference's own by more than one expert, to
PICKS_APART.  Six limits:
- PREFILL_APART: the prefill's last row, the largest over the sample (the
  prompt path alone);
- DECODE_APART: a sequence's MEDIAN decoded row, the largest over the
  sample (the decode path: a fault of the cache or the tails is in every
  row);
- TIE_SIGMA: the largest margin of a served token under the reference
  row's maximum (a wrong token in a live slot of the window);
- TWIN_APART, PICK_EPSILON, PICKS_APART.
Control, which has to come out as NOT correct
(`benchmark/tools/lfm2_limit_readings.py`): the reference's matrices
rounded through int8, symmetric per output channel — the nearest precision
below the bfloat16 the configuration states; float8 e4m3 is read beside it.
The limits and their readings are below.

THE WEIGHTS.  `Served` draws the model from `--seed` by the description's
conventions, then draws each matrix again from a normal distribution of
the same standard deviation (`normal_matrices`): a uniform matrix has the
smallest largest-entry-over-rms of any, √3, which is the error of a
per-channel int8 step at its least — on such matrices int8 rounds about
as bfloat16 does and no limit could tell them apart (1.6-1.9x the sound
distances on a CPU stand-in).  On normal matrices int8 reads 3.0-3.4x the
sound distances on the v5e (the limits below).  A trained model's weights
have tails at least a normal's.
"""
import functools
import tempfile

import numpy as np

from benchmark import serving, serving_cached, serving_cohere2_moe, \
    serving_moe_hybrid
from benchmark.reference import lfm2_moe as reference

# Readings (v5e, the published widths, PERF.md section 6): sound
# on four seeds — the tool on 3141592655 and the cell on 3000000019,
# 3500000029 and 2147483901, 32 sequences of 507-8,015 tokens, 8 of them
# over 4,096, 3,574 served tokens, 944,920 (expert layer, token) rows —
# | int8 on the tool's eight:
# - the prompt program's last row 0.0380-0.0488 | 0.1255-0.1427: the
#   limit 0.077 stands 1.58x over the largest sound reading and 1.63x
#   under the smallest of int8;
# - a sequence's median decoded row 0.0412-0.0433 | 0.1342-0.1400: the
#   same 0.077, 1.78x over and 1.74x under;
# - the worst served token's margin 0.155-0.179 sigma (mean 0.0041-0.0048)
#   | 0.632: TIE_SIGMA = 0.34, 1.9x over and 1.86x under;
# - largest shortfall 0.0485-0.0625 | 0.1686: PICK_EPSILON = 0.10, 1.6x
#   over, 1.69x under (a maximum over ~2.5e5 rows);
# - rows whose sets differ by more than one expert 2.8e-4-3.95e-4 | 9.8e-3:
#   PICKS_APART = 2e-3, 5.1x over, 4.9x under.
# int8 fails by all five.  The twins read 0.0 on every run and every
# served token was the replay's own pick: TWIN_APART holds them to the bit
# (not a precision; `tests/test_tpu_compile.py` shows chip-free that the
# twins' floating-point instructions are the engine programs').
PREFILL_APART = 0.077
DECODE_APART = 0.077
TIE_SIGMA = 0.34
TWIN_APART = 1e-6
PICK_EPSILON = 0.10
PICKS_APART = 2e-3
SAMPLE = 8
LONG = 2
LONG_TOKENS = 4096
DECODED = 128


def model_config(cfg, eng):
    """`Lfm2MoeConfig` for the stage the configuration file states: its
    `layer_types` (the first `num_hidden_layers` of the published list),
    `num_experts` experts held from `first_held_expert` on."""
    from paddle_tpu.models import Lfm2MoeConfig
    return Lfm2MoeConfig.from_published(
        cfg, held=cfg["num_experts"], first_held=cfg["first_held_expert"],
        max_position=cfg["max_position_embeddings"],
        eos_id=cfg["eos_token_id"], bos_id=cfg["eos_token_id"],
        dtype=eng["dtype"], embed_init_rms=cfg.get("embed_init_rms", 0.05))


def normal_matrices(model, seed):
    """Each matrix the reference's controls round (`reference.MATRICES`:
    the mixers', the dense MLPs' and the experts' projections) drawn again
    from a normal of its own standard deviation, from `seed`, on the
    device, one at a time."""
    import jax
    import jax.numpy as jnp
    key = jax.random.key(int(np.random.default_rng([seed, 23]).integers(
        2**31)))
    owners = [o for blk in model.layers
              for o in (blk.mixer, getattr(blk, "mlp", None),
                        getattr(blk, "experts", None)) if o is not None]
    n = 0
    for owner in owners:
        for name in reference.MATRICES:
            p = getattr(owner, name, None)
            if p is None:
                continue
            old = p._value
            std = jnp.std(old.astype(jnp.float32))
            p._value = (jax.random.normal(jax.random.fold_in(key, n),
                                          old.shape, jnp.float32)
                        * std).astype(old.dtype)
            del old
            n += 1
    return n


class Served(serving.Served):
    """The system under test: the decoder behind `InferenceServer` (`post`
    and `close` are `serving.Served`'s)."""

    def __init__(self, run):
        import paddle_tpu
        import paddle_tpu.static as static
        from paddle_tpu.inference.server import InferenceServer
        from paddle_tpu.models import Lfm2MoeModel

        cfg, eng = run.config, run.config["engine"]
        self.cfg = dict(cfg, n_positions=eng["max_context"])
        paddle_tpu.seed(run.seed)            # the weights come from --seed
        self.model = Lfm2MoeModel(model_config(cfg, eng))
        normal_matrices(self.model, run.seed)
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
        self._replayed = {}

    def reference_params(self):
        return reference.params_of(self.model)

    def replayed(self, done):
        """`served_decode_logits` of `done`, kept: the controls of one
        sample compare the same replay."""
        key = tuple(id(req) for req, _ in done)
        if key not in self._replayed:
            self._replayed[key] = served_decode_logits(self, done)
        return self._replayed[key]

    @functools.cached_property
    def twins(self):
        """The engine's prefill and decode programs with the picks as one
        result more (`_with_picks`): (prefill, decode(columns, *args)), a
        decode program a bound, each traced once."""
        from paddle_tpu.jit import StaticFunction
        from paddle_tpu.serving.step_program import DECODE_CACHE_AT
        model, n = self.model, len(self.server.engine.kv_pool.state.names)
        prefill = StaticFunction(_with_picks(model.prefill_step, False),
                                 layer=model, abstract_trace=True)
        decode = {}

        def decode_at(columns, *args):
            if columns not in decode:
                decode[columns] = StaticFunction(
                    _with_picks(functools.partial(model.decode_step,
                                                  columns=columns), True),
                    layer=model, abstract_trace=True,
                    donate_args=range(DECODE_CACHE_AT, DECODE_CACHE_AT + n))
            return decode[columns](*args)
        return prefill, decode_at


Sampler = serving_cached.Sampler
moe_counters = serving_moe_hybrid.moe_counters
warm_up = serving.warm_up


def conclude(run, served, sampler, last, done, moe_first):
    """`serving_pangu_ultra_moe.conclude` for this model: counters over the
    window (the routed experts' and the conv tails' among them), the
    engine's forwards inside the traced slice, and the checks that decide
    `correct`.  `done`: (request, tokens) of every reply."""
    from paddle_tpu.serving.metrics import serving_stats
    run.samples["kv_pages_used_share"] = sampler.page_samples
    run.samples["state_slots_used_share"] = sampler.state_samples
    moe_now = moe_counters()
    moe = {k: moe_now[k] - moe_first[k] for k in moe_now}
    run.counters.update(serving.delta(last, sampler.first),
                        max_slots=served.max_slots,
                        **{"moe." + k: v for k, v in moe.items()})
    stats = serving_stats()
    run.counters["state.conv_bytes"] = int(
        stats.get("serving.state.conv_bytes", 0))
    run.log(f"counters over the window: {run.counters}; "
            f"{last['queue_depth']} queued at its end; steps with the "
            f"state in place since start "
            f"{int(stats.get('serving.gen.state_in_place', 0))} of "
            f"{int(stats.get('serving.gen.steps', 0))}")
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
        run.log("reference: " + describe(got))
    run.checks.update(
        replies_well_formed=serving.check_replies(served, done),
        matches_reference=got is not None and within_limits(got))
    run.correct = bool(done)


def describe(got):
    """`summary`'s readings, each beside its limit, on one line."""
    return (f"{got['sequences']} sequences replayed through the engine's "
            f"own programs ({got['long']} over {LONG_TOKENS} tokens, lengths "
            f"{got['lengths']}): the prompt program's last row "
            f"{got['prefill_each']} of the row's spread (limit "
            f"{PREFILL_APART}), a sequence's median decoded row "
            f"{got['decode_each']} (limit {DECODE_APART}); the served "
            f"tokens' margins under the reference's row maximum: worst "
            f"{got['worst']} sigma (limit {TIE_SIGMA}), mean {got['mean']}; "
            f"{got['tokens_off']} of {got['tokens']} served tokens are not "
            f"the replay's own pick; the twins' logits "
            f"{got['twin_apart']} from the engine programs' (limit "
            f"{TWIN_APART}); router over {got['rows']} (layer, token) rows: "
            f"largest shortfall {got['shortfall']} (limit {PICK_EPSILON}), "
            f"{got['apart']} of the rows differ by more than one expert "
            f"(limit {PICKS_APART}), {got['differ']} at all")


def _with_picks(step, decode):
    """`step` as `StepPrograms` wraps it (`step_program._with_greedy`),
    with the experts each expert layer picked (`picks=True`) as one result
    more, last."""
    from paddle_tpu.serving.step_program import _with_greedy
    kept = []

    def picking(*args):
        *out, picks = step(*args, picks=True)
        kept.append(picks)
        return tuple(out)

    greedy = _with_greedy(picking, counted=True, ids_as_picked=decode)

    def twin(*args):
        out = greedy(*args)
        return (*out, kept.pop())
    return twin


def _replay(served, batch, prefill, decode):
    """`batch` ((request, tokens), at most `max_slots`) teacher-forced
    through `prefill(ids, lengths, last)` a prompt into a slot of the
    pool's arrays and `decode(columns, ids, lengths, active, *arrays)` all
    rows side by side, each row given the token the engine served at its
    own length up to `_compared`'s end; a row that has ended goes in idle.
    Both return `StepPrograms`' tuple, with the picks last where
    `picks`.  A sequence: (float32 logits [rows, vocabulary], row 0 the
    prefill's; the results' last entries, or None)."""
    import paddle_tpu
    from paddle_tpu.dygraph.base import no_grad
    from paddle_tpu.dygraph.tensor import Tensor
    engine = served.server.engine
    state = engine.kv_pool.state
    slots, extra = engine.max_slots, len(engine.step_programs.counters)
    n_cache = len(state.names)

    def device(*arrays):
        return [paddle_tpu.to_tensor(a) for a in arrays]

    rows, lasts = [], []
    for slot, (req, _) in enumerate(batch):
        p = len(req.prompt)
        ids = np.zeros((1, min(serving.next_pow2(p, engine._kv_floor),
                               int(engine.config.max_position))), np.int32)
        ids[0, :p] = req.prompt
        with no_grad():
            logits, _, *made = prefill(*device(
                ids, np.asarray([p], np.int32), np.asarray([p - 1], np.int32)))
        state.install(slot, **{n: t._value
                               for n, t in zip(state.names, made)})
        rows.append([np.asarray(logits.numpy(), np.float32)[0]])
        lasts.append([made[n_cache:]])
    for t in range(max(_compared(req, toks) - len(req.prompt)
                       for req, toks in batch)):
        ids = np.zeros(slots + extra, np.int32)
        lengths = np.zeros(slots, np.int32)
        active = np.zeros(slots, np.int32)
        for slot, (req, toks) in enumerate(batch):
            at = len(req.prompt) + t
            if at < _compared(req, toks):   # the token served at `at`
                ids[slot], lengths[slot], active[slot] = toks[at], at, 1
        old = list(state.arrays.values())
        columns = serving.next_pow2(int(lengths.max()), engine._kv_floor)
        with no_grad():
            # the arrays go in as tensors: a raw array would be taken as
            # data and copied through the host (`StaticFunction`)
            logits, _, *new = decode(columns, *device(ids, lengths, active),
                                     *[Tensor(a) for a in old])
        if not all(a.is_deleted() for a in old):
            raise RuntimeError("the replay's decode step copied the cache "
                               "arrays it was given")
        state.rebind(**{n: a._value
                        for n, a in zip(state.names, new[:n_cache])})
        logits = np.asarray(logits.numpy(), np.float32)
        for slot in np.flatnonzero(active):
            rows[slot].append(logits[slot])
            lasts[slot].append(new[n_cache:])
    return [(np.stack(r), last) for r, last in zip(rows, lasts)]


def served_decode_logits(served, done):
    """What the engine's own programs (`engine.step_programs`) and their
    twins with the picks give for the sequences of `done`, replayed after
    the window while the engine is idle, `max_slots` at a time (`_replay`:
    each prompt through the prefill program of its bucket, then the rows
    side by side through the decode program of the engine's bound).  A
    sequence: (the engine programs' logits [rows, vocabulary], row t the
    one answer token t was picked from; the twins' logits, the same rows;
    int32 [expert layers, `_compared` tokens, k], the experts each token
    was routed to where those logits were computed — the prompt's by the
    prefill, each answer token's by the decode step that took it)."""
    engine = served.server.engine
    steps = engine.step_programs
    twin_prefill, twin_decode = served.twins
    out = []
    for first in range(0, len(done), engine.max_slots):
        batch = done[first:first + engine.max_slots]
        own = _replay(served, batch, steps.prefill,
                      lambda columns, *a: steps.decode(*a, columns=columns))
        twin = _replay(served, batch, twin_prefill, twin_decode)
        for slot, ((req, toks), (l_own, _), (l_twin, picked)) in enumerate(
                zip(batch, own, twin)):
            p = len(req.prompt)
            layers, _, _, k = picked[0][-1].shape
            seq = np.zeros((layers, _compared(req, toks), k), np.int32)
            seq[:, :p] = np.asarray(picked[0][-1].numpy())[:, 0, :p]
            for t, last in enumerate(picked[1:]):       # [Le, S, 1, k]
                seq[:, p + t] = np.asarray(last[-1].numpy())[:, slot, 0]
            out.append((l_own, l_twin, seq))
    return out


def _compared(req, toks):
    """The end of the positions whose logits the check compares: the
    prompt's last and the first `DECODED` the answer decoded (the last
    token's row picked nothing)."""
    return min(len(toks) - 1, len(req.prompt) + DECODED)


def _spread(rows):
    return np.linalg.norm(rows - rows.mean(-1, keepdims=True), axis=-1)


def readings(served, done, weights_as=None):
    """Each sequence of `done` replayed through the engine's programs
    (`served_decode_logits`) and teacher-forced through the plain
    reference, GIVEN the experts the twins picked for each token: {"rows"
    [rows] the distance of each engine logits row from the reference's (row
    0 the prefill's), "margins" [rows] the served token's margin under the
    reference row's maximum in its standard deviations, "off" [rows]
    whether the served token is not the replayed row's own pick (an exact
    tie aside), "twin" the largest distance of a twin's row from the
    engine program's, "shortfall" and "apart" [expert layers, `_compared`
    tokens], "length"}.  `weights_as`: the control, on the reference's
    side."""
    params, out = served.reference_params(), []
    for (req, toks), (l_own, l_twin, picks) in zip(
            done, served.replayed(done)):
        n_prompt, upto = len(req.prompt), _compared(req, toks)
        width = serving.next_pow2(upto)
        padded = np.zeros(width, np.int32)
        padded[:upto] = toks[:upto]
        own, short = [], []
        # pads route anywhere: causal layers
        picks = np.pad(picks, ((0, 0), (0, width - upto), (0, 0)))
        ref = np.asarray(reference.logits(
            params, padded, served.cfg, weights_as=weights_as, picks=own,
            forced=picks, shortfall=short,
            rows=np.arange(n_prompt - 1, upto)))
        served_next = np.asarray(toks[n_prompt:upto + 1], np.int64)
        at = np.arange(len(served_next))
        out.append({
            "rows": np.linalg.norm(l_own - ref, axis=-1) / _spread(ref),
            "margins": (ref.max(-1) - ref[at, served_next]) / ref.std(-1),
            "off": l_own[at, served_next] < l_own.max(-1),
            "twin": float(np.max(np.linalg.norm(l_twin - l_own, axis=-1)
                                 / _spread(l_own))),
            "shortfall": np.stack([np.asarray(x)[:upto] for x in short]),
            "apart": np.asarray([
                [len(set(a) - set(b)) for a, b in zip(sa[:upto], ra[:upto])]
                for sa, ra in zip(picks, np.asarray(own))]),
            "length": len(toks)})
    return out


def summary(per_sequence):
    """What `within_limits` judges over `readings` (as
    `serving_pangu_ultra_moe.summary`, at this cell's `LONG_TOKENS`), with
    the served tokens' margins ("worst", "mean"), "tokens_off" of
    "tokens", and the twins' "twin_apart"."""
    apart = np.concatenate([r["apart"] for r in per_sequence], axis=1)
    short = np.concatenate([r["shortfall"] for r in per_sequence], axis=1)
    margins = np.concatenate([r["margins"] for r in per_sequence])
    prefill = [float(r["rows"][0]) for r in per_sequence]
    decode = [float(np.median(r["rows"][1:])) for r in per_sequence
              if len(r["rows"]) > 1]
    lengths = [r["length"] for r in per_sequence]
    return {"prefill": max(prefill),
            "prefill_each": [round(x, 5) for x in prefill],
            "decode": max(decode) if decode else None,
            "decode_each": [round(x, 5) for x in decode],
            "worst": float(margins.max()), "mean": float(margins.mean()),
            "tokens": int(margins.size),
            "tokens_off": int(sum(r["off"].sum() for r in per_sequence)),
            "twin_apart": max(r["twin"] for r in per_sequence),
            "shortfall": float(short.max()), "rows": int(apart.size),
            "differ": float((apart > 0).mean()),
            "apart": float((apart > 1).mean()),
            "sequences": len(per_sequence), "lengths": lengths,
            "long": sum(n > LONG_TOKENS for n in lengths)}


def within_limits(got):
    """Whether `summary`'s readings pass all six limits."""
    return bool(got["prefill"] <= PREFILL_APART
                and (got["decode"] is None or got["decode"] <= DECODE_APART)
                and got["worst"] <= TIE_SIGMA
                and got["twin_apart"] <= TWIN_APART
                and got["shortfall"] <= PICK_EPSILON
                and got["apart"] <= PICKS_APART)


def check_against_reference(served, done, seed, sample=SAMPLE, keep=None,
                            weights_as=None):
    """`summary` of `readings` over a seeded sample of `sample` served
    sequences of which at least `LONG` pass `LONG_TOKENS` where the window
    served so many (`serving_cohere2_moe.sample_of`); None where nothing
    was served.  `keep`: a list that receives the sample's `readings`;
    `weights_as`: the control for the reference."""
    if not done:
        return None
    chosen = serving_cohere2_moe.sample_of(done, seed, sample, LONG_TOKENS,
                                           long=LONG)
    per_sequence = readings(served, [done[i] for i in chosen],
                            weights_as=weights_as)
    if keep is not None:
        keep.extend(per_sequence)
    return summary(per_sequence)
