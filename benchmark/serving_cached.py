"""What `serving.py` does for GPT, for a model that states its cache
(`cache_spec`) and serves through the engine's compiled step route: build
it through the public entry points, the counters over a window, and the
comparison with its plain reference that decides `correct`.  What is
generic is imported from `serving.py` (`warm_up`, `Sampler`, `delta`,
`check_replies`, `next_pow2`); only what is GPT's by name there (`Served`'s
construction, `slice_work`, `check_against_reference`) has a twin here.
"""
import tempfile
import time

import numpy as np

from benchmark import serving
from benchmark.reference import granite_hybrid as reference

# Served tokens are compared with the float32 reference's logits rows: a
# served token's reference logit may lie some row standard deviations under
# its row's maximum (its MARGIN), because the served path rounds products
# and activations to bfloat16 where the reference keeps float32 and greedy
# chains part where two logits are close.  How far is measured, not
# assumed (PERF.md section 6 has every reading; v5e, the published widths):
# - over a sample of SAMPLE = 16 served sequences (~1,100 tokens) the MEAN
#   margin of the served path read 0.0031-0.0049 in the cell's own runs
#   (six seeds, final tree; 0.00370 and 0.00375 over 16 and 32 sequences
#   of two more), and the same comparison with the reference's matrices
#   rounded through int8 — the nearest precision below the bfloat16 the
#   configuration states — read 0.0088 and 0.0082: MEAN_SIGMA = 0.0066
#   sits between them, 1.34x over the largest served reading (four of its
#   standard deviations over its mean) and 1.24x under the smaller int8
#   one, so a weight precision lower than stated fails;
# - the WORST margin of one token is a tail statistic and does not separate
#   the two (served path: up to 0.177 over 2,201 tokens; int8: 0.107-0.295
#   over samples of 4 sequences), so TIE_SIGMA = 0.35 is only the guard for
#   gross faults — a wrong column, a missed mask on pads, the wrong
#   attention multiplier move logits by whole sigmas — at twice the largest
#   margin seen.
TIE_SIGMA = 0.35
MEAN_SIGMA = 0.0066
SAMPLE = 16


class Served(serving.Served):
    """The system under test: the hybrid decoder behind `InferenceServer`
    (`post` and `close` are `serving.Served`'s)."""

    def __init__(self, run):
        import paddle_tpu
        import paddle_tpu.static as static
        from paddle_tpu.inference.server import InferenceServer
        from paddle_tpu.models import GraniteHybridConfig, GraniteHybridModel

        cfg, eng = run.config, run.config["engine"]
        # `n_positions` is what serving.warm_up caps prompt buckets with
        self.cfg = dict(cfg, n_positions=eng["max_context"])
        paddle_tpu.seed(run.seed)            # the weights come from --seed
        self.model = GraniteHybridModel(GraniteHybridConfig.from_published(
            cfg, eos_id=cfg["eos_token_id"], bos_id=cfg["eos_token_id"],
            dtype=eng["dtype"]))
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


class Sampler(serving.Sampler):
    """`serving.Sampler`, which also keeps the share of recurrent-state
    slots in use at each tick."""

    def __init__(self, run, t0):
        self.state_samples = []
        super().__init__(run, t0)

    def _loop(self):
        from paddle_tpu.serving.metrics import gauge_value
        while not self._stop.is_set():
            snap = serving.read_counters()
            self.page_samples.append(snap["kv_pages_used_share"])
            self.state_samples.append(
                gauge_value("state.slots_used")
                / max(1.0, gauge_value("state.slots_total")))
            edge = self._run.slice.poll(time.perf_counter() - self._t0)
            if edge:
                self.at_edge[edge] = snap
            self._stop.wait(self.TICK_S)


def conclude(run, served, sampler, last, done):
    """`serving.conclude` for this model: counters over the window, the
    engine's forwards inside the traced slice, and the checks that decide
    `correct`.  `done`: (request, tokens) of every reply."""
    run.samples["kv_pages_used_share"] = sampler.page_samples
    run.samples["state_slots_used_share"] = sampler.state_samples
    run.counters.update(serving.delta(last, sampler.first),
                        max_slots=served.max_slots)
    run.log(f"counters over the window: {run.counters}; "
            f"{last['queue_depth']} queued at its end")
    edges = sampler.at_edge
    if "start" in edges and "stop" in edges:
        d = serving.delta(edges["stop"], edges["start"])
        prefills = max(0, d["gen.admitted"] - (
            edges["stop"]["queue_depth"] - edges["start"]["queue_depth"]))
        run.slice_units = (d["gen.steps"] + prefills) or None
        run.log(f"slice: {d['gen.steps']} decode steps, {prefills} "
                f"prefills, {d['gen.tokens']} decoded rows")
    got = check_against_reference(served, done, run.seed)
    run.log(f"reference: served-token margins over a sample of "
            f"{min(SAMPLE, len(done))}: worst {got and got[0]} sigma (limit "
            f"{TIE_SIGMA}), mean {got and got[1]} sigma (limit "
            f"{MEAN_SIGMA})")
    run.checks.update(
        replies_well_formed=serving.check_replies(served, done),
        matches_reference=got is not None and got[0] <= TIE_SIGMA
        and got[1] <= MEAN_SIGMA)
    run.correct = bool(done)


def margins(served, done, weights_as=None):
    """Every served token's margin under the plain reference, in row
    standard deviations: the sequences of `done` teacher-forced through
    `reference/granite_hybrid.py` on the model's own weights, the served
    token's reference logit against its row's maximum.  Each sequence is
    padded to a power of two (a causal model's earlier rows do not see the
    pads), so the reference compiles a handful of lengths."""
    params, out = served.reference_params(), []
    for req, toks in done:
        n_prompt, n = len(req.prompt), len(toks)
        padded = np.zeros(serving.next_pow2(n), np.int32)
        padded[:n] = toks
        logits = np.asarray(reference.logits(params, padded, served.cfg,
                                             weights_as=weights_as))
        for t in range(n_prompt - 1, n - 1):
            row = logits[t]
            out.append(float(row.max() - row[toks[t + 1]])
                       / float(row.std()))
    return np.asarray(out)


def check_against_reference(served, done, seed, sample=SAMPLE,
                            weights_as=None):
    """`serving.check_against_reference`'s rule on this model's reference:
    (worst, mean) of `margins` over a seeded sample of served sequences,
    held to TIE_SIGMA and MEAN_SIGMA; None where nothing was served."""
    if not done:
        return None
    rng = np.random.default_rng([seed, 7])
    picks = rng.choice(len(done), size=min(sample, len(done)), replace=False)
    got = margins(served, [done[int(i)] for i in picks], weights_as)
    return (float(got.max()), float(got.mean())) if got.size else (0.0, 0.0)
