"""What `serving_cached.py` does for granite, for the `nemotron_h` decoder
with latent routed experts, one chip's share of it: build it through the
public entry points and compare what it served with its plain reference —
the comparison that decides `correct`.  What is generic is imported
(`serving.Served`'s `post` / `close`, `serving.delta` / `check_replies` /
`next_pow2`, `serving_cached.Sampler`); what is granite's by name there
(`Served`'s construction, the reference and its limits, `conclude`) has a
twin here.
"""
import tempfile

import numpy as np

from benchmark import serving, serving_cached
from benchmark.reference import nemotron_h as reference

# Served tokens are compared with the float32 reference's logits rows, by
# `serving_cached`'s rule: a served token's reference logit may lie some
# row standard deviations under its row's maximum (its MARGIN), because the
# served path rounds products and activations to bfloat16 where the
# reference keeps float32, and greedy chains part where two logits are
# close.  This model has a discontinuity that rule cannot carry: the gate.
# It is float32 in both, but the served gate reads bfloat16 activations, so
# where a 22nd and a 23rd score lie a rounding apart the two pick different
# experts, and a different expert is a whole expert's contribution in the
# residual.  Left to its own picks the reference read a mean margin of
# 0.018-0.021 sigma for the served path and 0.027-0.031 with its matrices
# rounded through int8: the flipped picks were the noise under both, and
# no limit stood between them with room (review of PR 31).  So the two
# things are held apart, each to its own limit:
# - THE PICKS.  The served gate's picks are read back after the window
#   (`served_picks`: the model's own `routes`, the ops `prefill_step`
#   runs, over the sampled sequences) and the reference is GIVEN them: it
#   weights those experts with its own float32 `s`, so its residual follows
#   the served one and every expert layer's gate is compared on the same
#   layer input.  A served pick may lie at most PICK_EPSILON under the
#   reference's own 22nd-best `s + b` (`shortfall`; scores are in (0, 1)),
#   and at most PICKS_APART of the (expert layer, token) rows may differ
#   from the reference's set in more than one expert: a flipped last pick
#   is a tie, more is a fault (a missed mask, a wrong sort, a stale bias).
# - THE LOGITS.  With the picks given, the margins fall to granite's
#   (MEAN_SIGMA and TIE_SIGMA, that comparison's rule).
# The same comparison with the reference's matrices rounded through int8 —
# the nearest precision below the bfloat16 the configuration states — has
# to come out as not correct, by one of the four limits
# (`benchmark/tools/moe_limit_readings.py` runs both through
# `check_against_reference` and `within_limits`).
# Readings (v5e, the published widths; 64 served sequences = ~4,450 served
# tokens and 65,000-77,000 (expert layer, token) rows a run; PERF.md
# section 6 has each by seed).  Sound: the tool on three seeds (call H of
# PR 31) and the cell on four more (call I), seven readings; through int8:
# the tool on the first three, sound | control:
# - rows whose sets differ by more than one expert 0.42-0.47% | 1.77 /
#   1.88 / 1.98% (by layer 0.05-0.10% in the first, 0.7-1.0% in the fifth,
#   as the residual's rounding adds up | 0.2-0.4% to 3.3-3.6%): THE LIMIT
#   THE CONTROL FAILS, 3.7x between the two, so PICKS_APART = 0.9% stands
#   1.9x over the largest sound reading and 2x under the smallest of the
#   control (rows that differ at all: 21.3-21.9% | 34.4-34.9%; the mean
#   shortfall 0.000200-0.000202 | 0.000537-0.000551);
# - largest shortfall 0.0065-0.0082 | 0.0110-0.0149: a maximum over ~70,000
#   rows, so PICK_EPSILON = 0.02 (2.4x the largest) guards gross faults: a
#   pick the reference ranks well under its 22nd reads 0.05-0.5;
# - mean margin 0.0048-0.0058 sigma (0.0203 with the picks NOT given, same
#   sequences: the ties were three quarters of it) | 0.0055-0.0058.  What is
#   left is not arithmetic either: a sequence's FIRST answer token, the
#   prefill's, whose picks `routes` repeats exactly, reads 0.00002-0.00026 |
#   0.00016-0.00082; the decoded tokens' picks are those of the one-token
#   step, which `routes` (the scan's path) repeats only up to ITS ties
#   (inferred: int8 raises the residual's error, the mean shortfall, 2.7x
#   and the margins by 15%, so the margins are not that error's).  So the
#   margins do not separate the control (1.15x) and are not asked to:
#   MEAN_SIGMA = 0.01 (1.7x the largest sound reading, half of what a
#   reference left to its own picks reads) and TIE_SIGMA = 1.5 (worst token
#   0.40-0.66 | 0.42-0.43; a tail statistic, 2.3x) guard the served tokens
#   against gross faults — a wrong column, a missed mask on pads, the
#   experts of another chip move logits by whole sigmas, and the mean with
#   them.
TIE_SIGMA = 1.5
MEAN_SIGMA = 0.01
PICK_EPSILON = 0.02
PICKS_APART = 0.009
SAMPLE = 64


def model_config(cfg, eng):
    """`NemotronHConfig` for the share the configuration file states: the
    router at its published width, `n_routed_experts` experts held from
    `first_held_expert` on, the first `vocab_size` rows of the
    vocabulary, the pattern as the file cuts it."""
    from paddle_tpu.models import NemotronHConfig
    published = dict(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"])
    return NemotronHConfig.from_published(
        published, held_experts=cfg["n_routed_experts"],
        first_held=cfg["first_held_expert"], vocab_rows=cfg["vocab_size"],
        max_position=cfg["max_position_embeddings"],
        eos_id=cfg["eos_token_id"], bos_id=cfg["eos_token_id"],
        dtype=eng["dtype"])


class Served(serving.Served):
    """The system under test: the decoder behind `InferenceServer` (`post`
    and `close` are `serving.Served`'s)."""

    def __init__(self, run):
        import paddle_tpu
        import paddle_tpu.static as static
        from paddle_tpu.inference.server import InferenceServer
        from paddle_tpu.models import NemotronHModel

        cfg, eng = run.config, run.config["engine"]
        # `n_positions` is what serving.warm_up caps prompt buckets with
        self.cfg = dict(cfg, n_positions=eng["max_context"])
        paddle_tpu.seed(run.seed)            # the weights come from --seed
        self.model = NemotronHModel(model_config(cfg, eng))
        self.plan = static.page_budget(
            self.model, page_tokens=eng["page_tokens"],
            max_context=eng["max_context"], hbm_bytes=eng["hbm_bytes"],
            max_slots_cap=eng["max_slots_cap"])
        run.log("plan: " + ", ".join(f"{k}={self.plan[k]}" for k in (
            "pages", "max_slots", "max_context", "kv_bytes", "weight_bytes",
            "state_slot_bytes", "state_bytes", "workspace_bytes")))
        self._dir = tempfile.TemporaryDirectory()
        serving._save_stub_predictor(self._dir.name)
        self.server = InferenceServer(self._dir.name, generator=self.model,
                                      gen_kv_pool=self.plan)
        self.server.start()
        self.max_slots = int(self.plan["max_slots"])

    def reference_params(self):
        return reference.params_of(self.model)


Sampler = serving_cached.Sampler


def moe_counters():
    """The engine's `serving.moe.*` counters as they read now."""
    from paddle_tpu.serving.metrics import serving_stats
    snap = serving_stats()
    return {k: int(snap.get("serving.moe." + k, 0)) for k in (
        "pairs_routed", "pairs_held", "experts_touched", "expert_steps")}


def conclude(run, served, sampler, last, done, moe_first):
    """`serving_cached.conclude` for this model: counters over the window
    (the routed experts' among them), the engine's forwards inside the
    traced slice, and the checks that decide `correct`.  `done`: (request,
    tokens) of every reply."""
    run.samples["kv_pages_used_share"] = sampler.page_samples
    run.samples["state_slots_used_share"] = sampler.state_samples
    moe_now = moe_counters()
    moe = {k: moe_now[k] - moe_first[k] for k in moe_now}
    run.counters.update(serving.delta(last, sampler.first),
                        max_slots=served.max_slots,
                        **{"moe." + k: v for k, v in moe.items()})
    run.log(f"counters over the window: {run.counters}; "
            f"{last['queue_depth']} queued at its end")
    held = run.config["n_routed_experts"]
    if moe["expert_steps"] and moe["pairs_routed"]:
        run.log(f"experts over the window: {moe['pairs_held']} of "
                f"{moe['pairs_routed']} routed pairs landed on the {held} "
                f"held ({100.0 * moe['pairs_held'] / moe['pairs_routed']:.2f}"
                f"%), {moe['experts_touched'] / moe['expert_steps']:.2f} "
                "experts touched an expert layer a call")
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
                f"{got['rows']} (expert layer, token) rows of the sample: "
                f"largest shortfall {got['shortfall']} (limit "
                f"{PICK_EPSILON}), {got['apart']} of the rows differ by more "
                f"than one expert (limit {PICKS_APART}) and {got['differ']} "
                "at all; by layer "
                f"{got['by_layer']}")
        run.log(f"reference: served-token margins over a sample of "
                f"{min(SAMPLE, len(done))}: worst {got['worst']} sigma "
                f"(limit {TIE_SIGMA}), mean {got['mean']} sigma (limit "
                f"{MEAN_SIGMA})")
    run.checks.update(
        replies_well_formed=serving.check_replies(served, done),
        matches_reference=got is not None and within_limits(got))
    run.correct = bool(done)


def served_picks(served, done):
    """The experts the SERVED gate picks for every token of the sequences
    of `done`, a sequence [expert layers, T, k]: the model's own `routes`
    (the layers and ops `prefill_step` runs, bfloat16 activations into the
    float32 gate) compiled once at the engine's longest context, each
    sequence teacher-forced through it padded to that length."""
    import paddle_tpu
    from paddle_tpu.dygraph.base import no_grad
    from paddle_tpu.jit import StaticFunction
    routes = StaticFunction(served.model.routes, layer=served.model,
                            abstract_trace=True)
    width, out = served.cfg["n_positions"], []
    for _, toks in done:
        n = len(toks)
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = toks
        with no_grad():
            got = routes(paddle_tpu.to_tensor(padded),
                         paddle_tpu.to_tensor(np.asarray([n], np.int32)))
        out.append(np.asarray(got.numpy())[:, 0, :n])
    return out


def readings(served, done, weights_as=None, forced=True):
    """Each sequence of `done` teacher-forced through the plain reference,
    GIVEN the served gate's picks (`forced` False: left to its own, the
    margins only).  A sequence: {"margins": every served token's margin in
    row standard deviations (`serving_cached.margins`' rule), "shortfall"
    [expert layers, T]: how far the smallest reference score `s + b` of a
    served pick lies under the reference's own k-th best, "apart" [expert
    layers, T]: how many experts of the served set the reference's own set
    lacks — both gates on the same layer input}."""
    params, out = served.reference_params(), []
    given = served_picks(served, done) if forced else [None] * len(done)
    for (req, toks), picks in zip(done, given):
        n_prompt, n = len(req.prompt), len(toks)
        width = serving.next_pow2(n)
        padded = np.zeros(width, np.int32)
        padded[:n] = toks
        own, short = [], []
        if picks is not None:           # pads route anywhere: causal layers
            picks = np.pad(picks, ((0, 0), (0, width - n), (0, 0)))
        logits = np.asarray(reference.logits(
            params, padded, served.cfg, weights_as=weights_as, picks=own,
            forced=picks, shortfall=short))
        got = {"margins": np.asarray([
            float(logits[t].max() - logits[t][toks[t + 1]])
            / float(logits[t].std()) for t in range(n_prompt - 1, n - 1)])}
        if picks is not None:
            got["shortfall"] = np.stack([np.asarray(x)[:n] for x in short])
            got["apart"] = np.asarray([
                [len(set(a) - set(b)) for a, b in zip(sa[:n], ra[:n])]
                for sa, ra in zip(picks, np.asarray(own))])
        out.append(got)
    return out


def summary(per_sequence):
    """What `within_limits` judges, over `readings` of some sequences:
    {"worst", "mean" margin in row sigmas, and where the picks were given
    "rows", "shortfall" (the largest), "differ" / "apart" (share of rows
    whose sets differ at all / by more than one expert), "by_layer": a
    layer's (share that differ, share by more than one, most experts of a
    row)}."""
    margins = np.concatenate([r["margins"] for r in per_sequence])
    got = {"worst": float(margins.max()) if margins.size else 0.0,
           "mean": float(margins.mean()) if margins.size else 0.0}
    if "apart" in per_sequence[0]:
        apart = np.concatenate([r["apart"] for r in per_sequence], axis=1)
        short = np.concatenate([r["shortfall"] for r in per_sequence], axis=1)
        got.update(
            rows=int(apart.size), shortfall=float(short.max()),
            differ=float((apart > 0).mean()), apart=float((apart > 1).mean()),
            by_layer=[(round(float((m > 0).mean()), 4),
                       round(float((m > 1).mean()), 5), int(m.max()))
                      for m in apart])
    return got


def within_limits(got):
    """Whether `summary`'s readings (picks given) pass all four limits."""
    return bool(got["worst"] <= TIE_SIGMA and got["mean"] <= MEAN_SIGMA
                and got["shortfall"] <= PICK_EPSILON
                and got["apart"] <= PICKS_APART)


def check_against_reference(served, done, seed, sample=SAMPLE,
                            weights_as=None, keep=None):
    """`summary` of `readings` over a seeded sample of served sequences
    (what `within_limits` holds to the four limits); None where nothing
    was served.  `keep`: a list that receives the sample's `readings`."""
    if not done:
        return None
    rng = np.random.default_rng([seed, 7])
    chosen = rng.choice(len(done), size=min(sample, len(done)), replace=False)
    per_sequence = readings(served, [done[int(i)] for i in chosen],
                            weights_as)
    if keep is not None:
        keep.extend(per_sequence)
    return summary(per_sequence)
