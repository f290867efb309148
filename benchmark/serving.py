"""What the two serving drivers share: building the system under test
through its public entry points, warming the cell's shapes, one HTTP
client call, the counters read over a window, and the comparison with the
plain reference that decides `correct`.
"""
import http.client
import json
import tempfile
import threading
import time

import numpy as np

from benchmark import work
from benchmark.reference import gpt2

TIE_SIGMA = 0.05    # PR 21's near-tie rule, see check_against_reference


def next_pow2(n, floor=16):
    """The engine's bucket rule (`core.compile_cache.next_pow2`, copied:
    the yardstick does not import the program's arithmetic)."""
    b = floor
    while b < n:
        b <<= 1
    return b


class Served:
    """The system under test: a GPT behind `InferenceServer`."""

    def __init__(self, run):
        import paddle_tpu
        import paddle_tpu.static as static
        from paddle_tpu.inference.server import InferenceServer
        from paddle_tpu.models import GPTConfig, GPTForGeneration, GPTModel

        cfg, eng = run.config, run.config["engine"]
        self.cfg = cfg
        paddle_tpu.seed(run.seed)            # the weights come from --seed
        self.model = GPTForGeneration(GPTModel(GPTConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            intermediate_size=cfg.get("n_inner"),
            max_position=cfg["n_positions"], bos_id=cfg["bos_token_id"],
            eos_id=cfg["eos_token_id"], dropout=0.0)))
        self.model.eval()
        weight_bytes = 4 * work.gpt_all_params(cfg)
        self.plan = static.page_budget(
            self.model, page_tokens=eng["page_tokens"],
            max_context=eng["max_context"], hbm_bytes=eng["hbm_bytes"],
            weight_bytes=weight_bytes, max_slots_cap=eng["max_slots_cap"])
        run.log(f"plan: pages={self.plan['pages']} max_slots="
                f"{self.plan['max_slots']} max_context="
                f"{self.plan['max_context']} kv_bytes="
                f"{self.plan['kv_bytes']} weight_bytes={weight_bytes}")
        self._dir = tempfile.TemporaryDirectory()
        _save_stub_predictor(self._dir.name)
        self.server = InferenceServer(self._dir.name, generator=self.model,
                                      gen_kv_pool=self.plan)
        self.server.start()
        self.max_slots = int(self.plan["max_slots"])

    def close(self):
        self.server.stop()
        self._dir.cleanup()

    def post(self, prompt, max_new, timeout_s):
        """One `/generate` call; returns the token list or raises."""
        conn = http.client.HTTPConnection(self.server.host, self.server.port,
                                          timeout=timeout_s)
        try:
            conn.request("POST", "/generate", body=json.dumps(
                {"input_ids": [int(t) for t in prompt],
                 "max_length": int(max_new)}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {body[:200]!r}")
            return json.loads(body)["output_ids"][0]
        finally:
            conn.close()

    def reference_params(self):
        """The model's own weights, on the device, in the order
        `reference/gpt2.py` names them."""
        by_name = {n: p._value for n, p in self.model.gpt.named_parameters()}
        flat = [by_name["wte.weight"], by_name["wpe.weight"]]
        for i in range(self.cfg["n_layer"]):
            b = f"blocks.{i}."
            for n in ("ln1", "attn.q_proj", "attn.k_proj", "attn.v_proj",
                      "attn.out_proj", "ln2", "fc1", "fc2"):
                flat += [by_name[b + n + ".weight"], by_name[b + n + ".bias"]]
        return flat + [by_name["ln_f.weight"], by_name["ln_f.bias"]]


def _save_stub_predictor(model_dir):
    """`InferenceServer` fronts a saved inference model (`/predict`); the
    generator rides beside it.  A one-fc program is the smallest one
    (copied from chip_smoke.py)."""
    import paddle_tpu.static as static
    from paddle_tpu.io.framework_io import save_inference_model
    from paddle_tpu.static import layers
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = layers.data("x", [-1, 8])
        out = layers.fc(x, 2)
    exe, scope = static.Executor(), static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
        save_inference_model(model_dir, ["x"], [out], exe, main)


# ---------------------------------------------------------------------------
# warm-up: one request per bucket the mix can reach, and no other
# ---------------------------------------------------------------------------
def reachable_buckets(mix, max_position):
    """(prefill buckets, decode buckets) the engine can be asked for by
    this mix: prompts pad to a power of two, a decode step pads its cache
    to the power of two over the longest live sequence."""
    p_lo, p_hi = _length_range(mix["prompt_tokens"])
    n_hi = _length_range(mix["new_tokens"])[1]
    cap = mix.get("max_total_tokens") or p_hi + n_hi
    prefill = sorted({min(next_pow2(p), max_position)
                      for p in range(p_lo, p_hi + 1)})
    decode = sorted({next_pow2(length)
                     for length in range(p_lo, min(p_hi + n_hi, cap))}) \
        if n_hi > 1 else []
    return prefill, decode


def _length_range(spec):
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["min"]), int(spec["max"])


def warm_up(served, run):
    """Send, one after another, the fewest requests that touch every
    reachable bucket, and check the engine saw exactly those."""
    from paddle_tpu.serving.metrics import serving_stats
    mix = run.traffic
    p_lo, p_hi = _length_range(mix["prompt_tokens"])
    prefill, decode = reachable_buckets(mix, served.cfg["n_positions"])
    rng = np.random.default_rng([run.seed, 99])
    sent = 0
    for b in sorted(set(prefill) | set(decode)):
        # the shortest prompt that pads to b; 3 new tokens make two decode
        # steps over a cache that pads to b as well
        p = min(max(p_lo, b // 2 + 1), p_hi)
        new = max(3, b // 2 + 3 - p) if b in decode else 1
        prompt = rng.integers(0, served.cfg["vocab_size"] - 1, p)
        served.post(prompt, new, timeout_s=1200.0)
        sent += 1
    want = len(prefill) + len(decode)
    got = int(serving_stats().get("serving.gen.kv_buckets", 0))
    run.log(f"warm-up: {sent} requests over prefill buckets {prefill} and "
            f"decode buckets {decode}; engine reports {got} buckets")
    if got != want:
        raise RuntimeError(
            f"warm-up touched {got} engine buckets, the mix reaches {want}")


# ---------------------------------------------------------------------------
# counters over a window
# ---------------------------------------------------------------------------
COUNTERS = ("gen.steps", "gen.tokens", "gen.prefill_tokens", "gen.admitted",
            "gen.completed", "gen.failed", "gen.rejected", "gen.timeout")


def read_counters():
    from paddle_tpu.serving.metrics import serving_stats
    snap = serving_stats()
    out = {c: int(snap.get("serving." + c, 0)) for c in COUNTERS}
    out["queue_depth"] = int(snap.get("serving.gen.queue.depth", 0))
    total = snap.get("serving.kv.pages_total", 0)
    out["kv_pages_used_share"] = \
        1.0 - snap.get("serving.kv.pages_free", total) / total if total \
        else 0.0
    return out


def delta(after, before):
    return {c: after[c] - before[c] for c in COUNTERS}


class Sampler:
    """A thread of the load generator's that, every TICK_S, reads the
    engine's counters, keeps the pool's used share, and polls the trace
    slice — off the thread that sends requests, because starting and
    stopping the profiler blocks for seconds."""
    TICK_S = 0.25

    def __init__(self, run, t0):
        self.first = read_counters()
        self.page_samples, self.at_edge = [], {}
        self._run, self._t0 = run, t0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop.is_set():
            snap = read_counters()
            self.page_samples.append(snap["kv_pages_used_share"])
            edge = self._run.slice.poll(time.perf_counter() - self._t0)
            if edge:
                self.at_edge[edge] = snap
            self._stop.wait(self.TICK_S)

    def stop(self):
        """Stop sampling; returns the counters as they read now."""
        self._stop.set()
        self._thread.join(timeout=120.0)
        self._run.slice.close()
        return read_counters()


def conclude(run, served, sampler, last, done, requests):
    """What both serving drivers do once their window is over: counters
    over the window, required work in the traced slice, and the checks
    that decide `correct`.  `done`: (request, tokens) of every reply;
    `requests`: what was sent, for the mix's mean lengths."""
    run.samples["kv_pages_used_share"] = sampler.page_samples
    run.counters.update(delta(last, sampler.first),
                        max_slots=served.max_slots)
    run.log(f"counters over the window: {run.counters}; "
            f"{last['queue_depth']} queued at its end")
    edges = sampler.at_edge
    if "start" in edges and "stop" in edges:
        slice_work(run, delta(edges["stop"], edges["start"]),
                   edges["stop"]["queue_depth"]
                   - edges["start"]["queue_depth"], requests)
    worst = check_against_reference(served, done, run.seed)
    run.log(f"reference: worst served-token margin {worst} sigma over a "
            f"sample of {min(4, len(done))} (limit {TIE_SIGMA})")
    run.checks.update(
        replies_well_formed=check_replies(served, done),
        matches_reference=worst is not None and worst <= TIE_SIGMA)
    run.correct = bool(done)


def slice_work(run, d_slice, queue_growth, requests):
    """Forward passes and required work inside the traced slice, from the
    engine's counters there and the mix's mean lengths (the engine has no
    spans yet, so single steps are not visible)."""
    prefills = max(0, d_slice["gen.admitted"] - queue_growth)
    forwards = d_slice["gen.steps"] + prefills
    if not forwards or not requests:
        return
    p = np.asarray([len(r.prompt) for r in requests], np.float64)
    n = np.asarray([r.max_new for r in requests], np.float64)
    decode_ctx = float(np.mean(p + n / 2.0))
    prefill_ctx = float(np.mean(p * p) / np.mean(p)) / 2.0
    context_sum = d_slice["gen.tokens"] * decode_ctx \
        + d_slice["gen.prefill_tokens"] * prefill_ctx
    run.slice_units = forwards
    run.work = work.gpt_forward_work(
        run.config, rows=d_slice["gen.tokens"] + d_slice["gen.prefill_tokens"],
        context_sum=context_sum, logit_rows=d_slice["gen.tokens"] + prefills,
        forwards=forwards)
    run.log(f"slice: {d_slice['gen.steps']} decode steps, {prefills} "
            f"prefills, {d_slice['gen.tokens']} decoded rows, "
            f"{d_slice['gen.prefill_tokens']} prompt tokens")


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def check_replies(served, done):
    """Every reply has its requested length (or ends at EOS), echoes its
    prompt and holds tokens in range.  `done`: (request, tokens)."""
    vocab, eos = served.cfg["vocab_size"], served.cfg["eos_token_id"]
    for req, out in done:
        p = len(req.prompt)
        full = len(out) == p + req.max_new
        if not (full or (p < len(out) < p + req.max_new and out[-1] == eos)):
            return False
        if list(out[:p]) != [int(t) for t in req.prompt]:
            return False
        if min(out) < 0 or max(out) >= vocab:
            return False
    return True


def check_against_reference(served, done, seed, sample=4):
    """Teacher-force a seeded sample of served sequences through the plain
    reference on the model's own weights: every served token's reference
    logit must be within TIE_SIGMA standard deviations of its row's maximum.

    Why a tie rule and not equality or a logits tolerance: the engine's
    replies are tokens, and two float32 forward passes of different shapes
    (a padded, cached, batched decode step against a full pass at highest
    precision) do not round alike on the chip, so greedy chains part at
    numeric ties (PR 21).  A wrong KV column, a wrong position or a lower
    precision than the configuration states moves logits by whole sigmas;
    0.05 sigma is two orders tighter than that and an order above the
    margins seen at ties.  Returns the largest margin seen, in sigmas."""
    if not done:
        return None
    rng = np.random.default_rng([seed, 7])
    picks = rng.choice(len(done), size=min(sample, len(done)), replace=False)
    params = served.reference_params()
    cfg = served.cfg
    worst = 0.0
    for i in picks:
        req, out = done[int(i)]
        n_prompt, n = len(req.prompt), len(out)
        padded = np.full(min(next_pow2(n), cfg["n_positions"]),
                         cfg["eos_token_id"], np.int32)
        padded[:n] = out
        logits = np.asarray(gpt2.logits(params, padded, cfg["n_layer"],
                                        cfg["n_head"]))
        for t in range(n_prompt - 1, n - 1):
            row = logits[t]
            margin = float(row.max() - row[out[t + 1]]) / float(row.std())
            worst = max(worst, margin)
    return worst


def drain(futures, limit_s):
    """Wait for what is still in flight, up to `limit_s` in all."""
    deadline = time.perf_counter() + limit_s
    for f in futures:
        left = deadline - time.perf_counter()
        if left <= 0:
            break
        try:
            f.result(timeout=left)
        except Exception:     # noqa: BLE001 — the caller counts failures
            pass
