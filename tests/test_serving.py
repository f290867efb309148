"""paddle_tpu.serving: dynamic batching + continuous-batching generation.

Covers the serving-tier contracts: K concurrent callers coalesce into
<= ceil(K/max_batch) device runs with row-exact results, queue-full and
deadline backpressure, monitor gauges/histograms, continuous-batching
decode equivalence with per-sequence generate(), and a threaded
end-to-end server pass."""
import contextlib
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.serving import (DynamicBatcher, QueueFullError,
                                DeadlineExceededError, BatcherStoppedError)
from paddle_tpu.serving import metrics


def test_batcher_coalesces_rows_exact():
    """12 callers / max_batch 4 -> exactly ceil(12/4)=3 device runs once
    the scheduler unblocks, every caller getting its own rows back."""
    sizes = []
    gate = threading.Event()

    def runner(feeds):
        if not gate.is_set():  # the plug request holds the scheduler
            gate.wait(10)
        else:
            sizes.append(feeds[0].shape[0])
        return [feeds[0] * 3.0, np.float32(7.0)]

    b = DynamicBatcher(runner, max_batch=4, max_wait_ms=0.0,
                       pad_to_bucket=False).start()
    try:
        plug = b.submit([np.zeros((1, 2), np.float32)])
        time.sleep(0.05)  # scheduler is now blocked inside the plug run
        futs = [b.submit([np.full((1, 2), float(i), np.float32)])
                for i in range(12)]
        gate.set()
        plug.result(timeout=10)
        outs = [f.result(timeout=10) for f in futs]
    finally:
        b.stop()
    assert sizes == [4, 4, 4], sizes
    for i, (rows, scalar) in enumerate(outs):
        np.testing.assert_array_equal(rows, np.full((1, 2), 3.0 * i))
        # batch-level (non-row) outputs are shared to every caller
        assert float(scalar) == 7.0
    assert metrics.counter("batch.coalesced") >= 3


def test_batcher_pow2_padding_and_mixed_shapes():
    """Ragged coalesced batches are padded to the pow2 bucket before the
    runner; requests with different row shapes never share a run."""
    sizes = []

    def runner(feeds):
        sizes.append(feeds[0].shape[0])
        return [feeds[0] + 1.0]

    b = DynamicBatcher(runner, max_batch=8, max_wait_ms=40.0).start()
    try:
        f1 = b.submit([np.zeros((2, 3), np.float32)])
        f2 = b.submit([np.ones((1, 3), np.float32)])
        f3 = b.submit([np.zeros((1, 5), np.float32)])  # other signature
        r1 = f1.result(timeout=10)[0]
        r2 = f2.result(timeout=10)[0]
        r3 = f3.result(timeout=10)[0]
    finally:
        b.stop()
    assert r1.shape == (2, 3) and np.all(r1 == 1.0)
    assert r2.shape == (1, 3) and np.all(r2 == 2.0)
    assert r3.shape == (1, 5)
    # 2+1 rows coalesced -> padded to 4; the [1,5] request ran alone
    assert 4 in sizes and 1 in sizes, sizes


def test_batcher_queue_full_and_deadline():
    release = threading.Event()

    def slow(feeds):
        release.wait(10)
        return [feeds[0]]

    b = DynamicBatcher(slow, max_batch=1, max_wait_ms=0.0,
                       max_queue=2).start()
    try:
        first = b.submit([np.zeros((1, 1), np.float32)])
        time.sleep(0.05)  # scheduler now blocked in `slow`
        expired = b.submit([np.zeros((1, 1), np.float32)], timeout_s=0.01)
        b.submit([np.zeros((1, 1), np.float32)])
        with pytest.raises(QueueFullError) as ei:
            b.submit([np.zeros((1, 1), np.float32)])
        assert ei.value.http_status == 503
        assert ei.value.retry_after_s > 0
        time.sleep(0.05)  # let the 10ms deadline lapse before release
        release.set()
        first.result(timeout=10)
        with pytest.raises(DeadlineExceededError):
            expired.result(timeout=10)
    finally:
        b.stop()
    # stopped batcher rejects synchronously
    with pytest.raises(BatcherStoppedError):
        b.submit([np.zeros((1, 1), np.float32)])
    assert metrics.counter("requests.timeout") >= 1


def test_backpressure_retry_after_is_jittered_and_load_scaled():
    """A fixed Retry-After marches every rejected client back in one
    synchronized wave (thundering herd); the hint must be load-scaled
    AND jittered so concurrent rejects decorrelate."""
    release = threading.Event()

    def slow(feeds):
        release.wait(10)
        return [feeds[0]]

    hints = []
    shallow_hints = []
    for depth in (2, 32):
        b = DynamicBatcher(slow, max_batch=1, max_wait_ms=50.0,
                           max_queue=depth).start()
        try:
            b.submit([np.zeros((1, 1), np.float32)])
            time.sleep(0.05)  # scheduler blocked inside `slow`
            for _ in range(depth):
                b.submit([np.zeros((1, 1), np.float32)])
            got = []
            for _ in range(24):
                with pytest.raises(QueueFullError) as ei:
                    b.submit([np.zeros((1, 1), np.float32)])
                got.append(ei.value.retry_after_s)
            (shallow_hints if depth == 2 else hints).extend(got)
        finally:
            release.set()
            b.stop(drain=False)
            release.clear()
    # jitter: repeated rejects at identical load must NOT repeat the hint
    assert len(set(hints)) > 1
    assert len(set(shallow_hints)) > 1
    # load scaling: a 16x deeper backlog earns a larger hint even at the
    # jitter extremes (bounds: base*[0.5, 1.5))
    assert min(hints) > max(shallow_hints)
    for h in hints + shallow_hints:
        assert h > 0
    # a draining batcher's rejection hint is jittered too, not 1.0 flat
    stopped = [BatcherStoppedError().retry_after_s for _ in range(16)]
    assert len(set(stopped)) > 1
    assert all(0.5 <= s <= 1.5 for s in stopped)


def test_batcher_error_fanout():
    def broken(feeds):
        raise RuntimeError("kernel exploded")

    b = DynamicBatcher(broken, max_batch=4, max_wait_ms=20.0).start()
    try:
        futs = [b.submit([np.zeros((1, 1), np.float32)])
                for _ in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="kernel exploded"):
                f.result(timeout=10)
    finally:
        b.stop()


def test_monitor_gauges_and_histograms():
    from paddle_tpu.core.monitor import (gauge_set, gauge_get,
                                         hist_observe, hist_snapshot,
                                         monitor_snapshot, stat_reset)
    gauge_set("t.depth", 5)
    gauge_set("t.depth", 3)
    assert gauge_get("t.depth") == 3
    assert hist_snapshot("t.lat")["count"] == 0
    for v in range(1, 101):
        hist_observe("t.lat", float(v))
    snap = hist_snapshot("t.lat")
    assert snap["count"] == 100 and snap["min"] == 1.0
    assert snap["max"] == 100.0
    assert abs(snap["p50"] - 50) <= 2
    assert abs(snap["p99"] - 99) <= 2
    full = monitor_snapshot("t.")
    assert full["t.depth"] == 3 and full["t.lat"]["count"] == 100
    stat_reset("t.depth")
    stat_reset("t.lat")
    assert gauge_get("t.depth") == 0
    assert hist_snapshot("t.lat")["count"] == 0


# ---------------------------------------------------------------------------
# Prometheus exposition (core/monitor.prometheus_text + /metrics)
# ---------------------------------------------------------------------------
_PROM_LINE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>.*)\})? (?P<value>\S+)$')
_PROM_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prometheus(text):
    """Minimal exposition-format parser: {(name, labels): value},
    {name: type}.  Raises on any line that violates the line grammar —
    the round-trip IS the conformance check."""
    series, types = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            types[name] = typ
            continue
        if line.startswith("#"):
            assert line.startswith("# HELP "), line
            continue
        m = _PROM_LINE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        labels = {}
        raw = m.group("labels")
        if raw:
            consumed = ",".join(f'{k}="{v}"'
                                for k, v in _PROM_LABEL.findall(raw))
            assert consumed == raw, f"malformed labels: {raw!r}"
            for k, v in _PROM_LABEL.findall(raw):
                labels[k] = re.sub(
                    r'\\(["\\n])',
                    lambda mm: {'"': '"', '\\': '\\', 'n': '\n'}[
                        mm.group(1)], v)
        series[(m.group("name"),
                tuple(sorted(labels.items())))] = float(m.group("value"))
    return series, types


def test_prometheus_text_spec_conformance_roundtrip():
    """HELP/TYPE lines, counter _total suffix, summary quantile series,
    and label escaping all survive a round-trip through a strict line
    parser."""
    from paddle_tpu.core.monitor import (prometheus_text, stat_add,
                                         gauge_set, hist_observe,
                                         stat_reset)
    stat_add("promtest.requests", 7)
    gauge_set("promtest.depth", 2.5)
    for v in range(1, 101):
        hist_observe("promtest.lat_ms", float(v))
    try:
        nasty = 'a"b\\c\nd'
        text = prometheus_text(prefix="promtest.",
                               labels={"rank": "0", "job": nasty})
        series, types = _parse_prometheus(text)
        assert types["promtest_requests_total"] == "counter"
        assert types["promtest_depth"] == "gauge"
        assert types["promtest_lat_ms"] == "summary"
        base = (("job", nasty), ("rank", "0"))
        assert series[("promtest_requests_total", base)] == 7
        assert series[("promtest_depth", base)] == 2.5
        q50 = series[("promtest_lat_ms",
                      tuple(sorted(base + (("quantile", "0.5"),))))]
        assert abs(q50 - 50) <= 2
        assert series[("promtest_lat_ms_count", base)] == 100
        assert series[("promtest_lat_ms_sum", base)] == 5050
        # every TYPE-declared metric has at least one sample line
        for name in types:
            assert any(k[0].startswith(name) for k in series), name
    finally:
        for n in ("promtest.requests", "promtest.depth",
                  "promtest.lat_ms"):
            stat_reset(n)


def test_server_metrics_scrape_live(tmp_path):
    """GET /metrics on the live inference server: text/plain exposition
    a scraper can parse, carrying the serving metrics the request
    traffic just minted."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_smoke
    from paddle_tpu.inference.server import InferenceServer
    xb, ref, out_name = serve_smoke.save_tiny_model(str(tmp_path))
    srv = InferenceServer(str(tmp_path), max_wait_ms=5.0)
    srv.start()
    try:
        base = f"http://{srv.host}:{srv.port}"
        _post(base + "/predict", {"inputs": {"x": xb[:1].tolist()}})
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            assert r.status == 200
            ctype = r.headers["Content-Type"]
            body = r.read().decode()
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        series, types = _parse_prometheus(body)
        assert types["serving_requests_completed_total"] == "counter"
        completed = series[("serving_requests_completed_total", ())]
        assert completed >= 1
        assert types["serving_latency_ms"] == "summary"
    finally:
        srv.stop()


def _tiny_gpt(vocab=30):
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    cfg = GPTConfig(vocab_size=vocab, hidden_size=16, num_layers=1,
                    num_heads=2, max_position=32, dropout=0.0)
    return GPTForGeneration(GPTModel(cfg))


def test_continuous_batching_matches_sequential_generate():
    """Sequences admitted into a shared fixed-slot batch (joining and
    leaving mid-decode) must reproduce per-sequence greedy generate()
    token for token."""
    import paddle_tpu.dygraph as dg
    from paddle_tpu.serving import ContinuousBatchingEngine
    rng = np.random.RandomState(3)
    prompts = [rng.randint(2, 30, (n,)).astype(np.int64)
               for n in (3, 5, 2)]
    with dg.guard():
        m = _tiny_gpt()
        m.eval()
        refs = [m.generate(p[None], max_length=4,
                           decode_strategy="greedy_search")[0]
                for p in prompts]
        # 2 slots, 3 requests: the third must join when a slot frees
        eng = ContinuousBatchingEngine(m, max_slots=2).start()
        try:
            futs = [eng.submit(p, max_length=4) for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
        finally:
            eng.stop()
    for ref, out in zip(refs, outs):
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert metrics.counter("gen.completed") >= 3
    assert metrics.counter("gen.steps") >= 1


def test_engine_rejects_bad_requests():
    import paddle_tpu.dygraph as dg
    from paddle_tpu.serving import ContinuousBatchingEngine
    with dg.guard():
        m = _tiny_gpt()
        eng = ContinuousBatchingEngine(m, max_slots=2)
        with pytest.raises(ValueError, match="beam"):
            eng.submit([2, 3], decode_strategy="beam_search")
        with pytest.raises(ValueError, match="max_position"):
            eng.submit(list(range(2, 30)), max_length=30)
        with pytest.raises(BatcherStoppedError):
            eng.submit([2, 3])  # not started
        eng.start()
        eng.stop()
        with pytest.raises(BatcherStoppedError):
            eng.submit([2, 3])


def test_server_stop_without_start(tmp_path):
    """stop() on a never-started server must not hang in shutdown()."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_smoke
    from paddle_tpu.inference.server import InferenceServer
    serve_smoke.save_tiny_model(str(tmp_path))
    srv = InferenceServer(str(tmp_path))
    done = threading.Event()

    def stopper():
        srv.stop(drain_timeout_s=1.0)
        done.set()

    t = threading.Thread(target=stopper, daemon=True)
    t.start()
    assert done.wait(10), "stop() hung on a never-started server"
    assert srv.status == "stopped"


def test_server_keepalive_survives_error_replies(tmp_path):
    """Early error replies (404 route) must drain the POST body, or the
    next request on the same keep-alive connection desyncs."""
    import sys, os, http.client
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_smoke
    from paddle_tpu.inference.server import InferenceServer
    xb, ref, out_name = serve_smoke.save_tiny_model(str(tmp_path))
    srv = InferenceServer(str(tmp_path))
    srv.start()
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=10)
        body = json.dumps({"inputs": {"x": xb[:1].tolist()}}).encode()
        conn.request("POST", "/nope", body,
                     {"Content-Type": "application/json"})
        assert conn.getresponse().read() and True  # 404, body drained
        # the SAME connection must still serve a real predict
        conn.request("POST", "/predict", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        reply = json.loads(resp.read())
        got = np.asarray(reply["outputs"][out_name]["data"]).reshape(
            reply["outputs"][out_name]["shape"])
        np.testing.assert_allclose(got, ref[:1], rtol=1e-4, atol=1e-6)
        conn.close()
    finally:
        srv.stop()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_server_end_to_end_threaded(tmp_path):
    """Concurrent /predict through the batcher (row-exact), /generate
    through the engine (greedy-equal), /stats, readiness /health, and
    graceful stop()."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_smoke
    import paddle_tpu.dygraph as dg
    from paddle_tpu.inference.server import InferenceServer

    xb, ref, out_name = serve_smoke.save_tiny_model(str(tmp_path))
    with dg.guard():
        gen = _tiny_gpt()
        gen.eval()
        seq_ref = gen.generate(np.array([[4, 9]], np.int64),
                               max_length=3)[0]
        srv = InferenceServer(str(tmp_path), max_wait_ms=10.0,
                              generator=gen, gen_slots=2)
        srv.start()
        try:
            base = f"http://{srv.host}:{srv.port}"
            with urllib.request.urlopen(base + "/health", timeout=10) as r:
                assert json.loads(r.read())["status"] == "ok"

            results = [None] * 6
            def client(i):
                k = i % xb.shape[0]
                reply = _post(base + "/predict",
                              {"inputs": {"x": xb[k:k + 1].tolist()}})
                o = reply["outputs"][out_name]
                results[i] = (k, np.asarray(o["data"]).reshape(o["shape"]))
            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(6)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for k, got in results:
                np.testing.assert_allclose(got, ref[k:k + 1],
                                           rtol=1e-4, atol=1e-6)

            g = _post(base + "/generate",
                      {"input_ids": [4, 9], "max_length": 3})
            assert g["output_ids"][0] == list(seq_ref)

            with urllib.request.urlopen(base + "/stats", timeout=10) as r:
                st = json.loads(r.read())
            assert st["status"] == "ok"
            assert st["serving"].get("serving.requests.completed", 0) >= 6
            assert "predictor_cache" in st

            # structured client error: missing input -> 400 + json body
            try:
                _post(base + "/predict", {"inputs": {}})
                assert False, "expected HTTPError"
            except urllib.error.HTTPError as e:
                assert e.code == 400
                body = json.loads(e.read())
                assert "error" in body and "type" in body
        finally:
            srv.stop()
        assert srv.status == "stopped"
        # post-stop: socket is closed, no handler raced server_close
        with pytest.raises(Exception):
            urllib.request.urlopen(base + "/health", timeout=2)


# ---------------------------------------------------------------------------
# tp-sharded decode: the 4×2-mesh engine vs single-chip greedy
# ---------------------------------------------------------------------------

def _tp_gpt(vocab=48):
    """4-head sibling of _tiny_gpt: the KV slab shards on heads, so the
    tp=2 matrix needs H % 2 == 0 with at least 2 heads per chip."""
    from paddle_tpu.models import GPTConfig, GPTModel, GPTForGeneration
    cfg = GPTConfig(vocab_size=vocab, hidden_size=16, num_layers=2,
                    num_heads=4, max_position=64, dropout=0.0)
    return GPTForGeneration(GPTModel(cfg))


@contextlib.contextmanager
def _seeded(seed):
    """Weights drawn inside come from `seed`, whatever ran before in this
    worker; the process-global generator and the default programs' seeds
    are put back, so whatever runs after draws what it drew before."""
    import paddle_tpu
    from paddle_tpu.core import generator
    from paddle_tpu.core.program import (default_main_program,
                                         default_startup_program)
    state = generator.get_rng_state()
    seeds = (default_main_program().random_seed,
             default_startup_program().random_seed)
    paddle_tpu.seed(seed)
    try:
        yield
    finally:
        generator.set_rng_state(state)
        default_main_program().random_seed, \
            default_startup_program().random_seed = seeds


def test_tp_sharded_engine_token_equal_matrix():
    """The ISSUE-19 equality matrix in one drain: a tp=2 engine with a
    planner-sized sharded pool, radix prefix retention, and a shallow
    speculative draft (partial acceptance forces real rollbacks) must
    reproduce the tp=1 paged engine token for token — greedy decode,
    radix-hit resume on a page-aligned shared head, and speculative
    verify/rollback all riding the sharded tables — and both pools
    must drain clean after the churn."""
    import paddle_tpu.dygraph as dg
    from paddle_tpu.serving import (ContinuousBatchingEngine, PagedKVPool,
                                    RadixPrefixCache, SpeculativeDecoder,
                                    stamp_draft)
    from paddle_tpu.static import page_budget
    rng = np.random.RandomState(17)
    # page-aligned shared head (page_tokens=4 -> exactly 2 pages) so the
    # repeat prompt resumes from retained radix pages, not cold prefill
    head = rng.randint(2, 48, (8,)).astype(np.int64)
    prompts = [np.concatenate([head, rng.randint(2, 48, (3,))
                               .astype(np.int64)]) for _ in range(2)]
    prompts += [rng.randint(2, 48, (n,)).astype(np.int64) for n in (3, 6)]
    prompts.append(prompts[0].copy())          # whole-prompt radix hit
    with dg.guard():
        # the outcome hangs on the weights (the shallow draft must be
        # partly right, partly wrong): drawn unseeded, it passed or
        # failed with whatever ran before in the worker
        with _seeded(1234):
            m = _tp_gpt()
        m.eval()
        plan1 = page_budget(m, page_tokens=4, max_context=64)
        ref_pool = PagedKVPool.from_plan(plan1)
        eng = ContinuousBatchingEngine(m, max_slots=2,
                                       kv_pool=ref_pool).start()
        try:
            refs = [np.asarray(eng.submit(p, max_length=6)
                               .result(timeout=120)) for p in prompts]
        finally:
            eng.stop()
        ref_pool.assert_drained()

        plan2 = page_budget(m, page_tokens=4, max_context=64,
                            tp_degree=2)
        pool = PagedKVPool.from_plan(plan2)
        radix = RadixPrefixCache(pool, low_watermark=2, high_watermark=4)
        # 1-of-2-layer draft: proposals diverge from the target, so the
        # sharded verify path must take BOTH branches (accept + rollback)
        spec = SpeculativeDecoder(stamp_draft(m, num_layers=1), k=2)
        eng = ContinuousBatchingEngine(m, max_slots=2, kv_pool=pool,
                                       prefix_cache=radix,
                                       speculative=spec).start()
        assert eng.tp_degree == 2
        try:
            outs = [np.asarray(eng.submit(p, max_length=6)
                               .result(timeout=300)) for p in prompts]
        finally:
            eng.stop()
    for i, (ref, out) in enumerate(zip(refs, outs)):
        np.testing.assert_array_equal(
            ref, out, err_msg=f"prompt {i} diverged on the tp=2 mesh")
    assert radix.hits >= 1, "page-aligned repeat never hit the radix tree"
    assert metrics.counter("spec.accepted") >= 1
    assert metrics.counter("spec.rollback_cols") >= 1, \
        "shallow draft produced no rollbacks — verify path untested"
    pool.assert_drained()
    radix.clear()
    pool.assert_drained()


def test_tp_decode_program_layout_is_v6xx_clean():
    """Every decode bucket shape (prefill, single-token decode, and the
    speculative verify window) must analyze clean under the V6xx
    sharding propagator on the 4×2 mesh — the gather-by-page-table view
    composes with the head-sharded cache feeds, col/row projections,
    and the c_concat KV gathers without a single diagnostic."""
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.serving import build_decode_program
    from paddle_tpu.static.layout_analysis import propagate_shardings
    cfg = GPTConfig(vocab_size=48, hidden_size=16, num_layers=2,
                    num_heads=4, max_position=64, dropout=0.0)
    for (B, lc, W) in ((1, 0, 8), (4, 16, 1), (4, 16, 3)):
        prog, _, _ = build_decode_program(cfg, batch=B, cache_len=lc,
                                          width=W, tp_degree=2)
        layout = propagate_shardings(prog, mesh_shape={"dp": 4, "tp": 2},
                                     batch=B)
        assert layout.diagnostics == [], \
            f"decode bucket B={B} lc={lc} W={W}: {layout.diagnostics}"


def test_tp2_serves_model_infeasible_at_tp1():
    """The ISSUE-19 'done' demo: under a pinned per-chip HBM budget the
    tp=1 page budget cannot even hold one decode slot — and the SAME
    budget at tp=2 carves a real pool that serves token-for-token equal
    to unconstrained single-chip greedy, pool drained clean."""
    import paddle_tpu.dygraph as dg
    import pytest as _pytest
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedKVPool
    from paddle_tpu.static import page_budget
    rng = np.random.RandomState(29)
    prompts = [rng.randint(2, 48, (n,)).astype(np.int64) for n in (4, 7)]
    with dg.guard():
        m = _tp_gpt()
        m.eval()
        weight_bytes = int(sum(np.asarray(p.numpy()).nbytes
                               for p in m.gpt.parameters()))
        # weights + ~2 KiB: tp=1 cannot place a single max-context slot
        hbm = weight_bytes + 2048
        with _pytest.raises(ValueError, match="not enough for one"):
            page_budget(m, page_tokens=4, max_context=64, hbm_bytes=hbm)
        plan = page_budget(m, page_tokens=4, max_context=64,
                           hbm_bytes=hbm, tp_degree=2)
        assert plan["pages"] >= 1
        refs = [np.asarray(m.generate(p[None], max_length=4,
                                      decode_strategy="greedy_search")[0])
                for p in prompts]
        pool = PagedKVPool.from_plan(plan)
        eng = ContinuousBatchingEngine(m, max_slots=1,
                                       kv_pool=pool).start()
        assert eng.tp_degree == 2
        try:
            outs = [np.asarray(eng.submit(p, max_length=4)
                               .result(timeout=300)) for p in prompts]
        finally:
            eng.stop()
    for ref, out in zip(refs, outs):
        np.testing.assert_array_equal(ref, out)
    pool.assert_drained()


# ---------------------------------------------------------------------------
# the engine's spans and the counters at the same sites
# ---------------------------------------------------------------------------
def test_engine_counters_move_at_the_span_sites():
    """An operator without a trace gets the trace's ratios from /stats:
    prefills and their queue wait, bytes over the host link each way, and
    bytes through the pool's gather / append."""
    import paddle_tpu.dygraph as dg
    from paddle_tpu.core.monitor import prometheus_text
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedKVPool
    names = ("gen.prefills", "gen.queue_wait_us", "gen.h2d_bytes",
             "gen.d2h_bytes", "kv.gather_bytes", "kv.append_bytes")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(2, 30, (n,)).astype(np.int64) for n in (5, 9, 3)]
    with dg.guard():
        with _seeded(99):
            m = _tiny_gpt()
        m.eval()
        pool = PagedKVPool(1, 2, 8, page_tokens=4, num_pages=64)
        before = {n: metrics.counter(n) for n in names}
        eng = ContinuousBatchingEngine(m, max_slots=2, kv_pool=pool).start()
        try:
            outs = [eng.submit(p, max_length=4) for p in prompts]
            outs = [np.asarray(f.result(timeout=120)) for f in outs]
        finally:
            eng.stop()
    pool.assert_drained()
    moved = {n: metrics.counter(n) - before[n] for n in names}
    assert moved["gen.prefills"] == len(prompts)
    assert all(v > 0 for v in moved.values()), moved
    # a prefill uploads ids (the mask is the model's own) and brings the
    # whole [1, bucket, vocab] fp32 logits down to keep one row
    assert moved["gen.d2h_bytes"] >= len(prompts) * 16 * 30 * 4
    # every generated token past the first appended one [L, H, Dh] K and V
    # column; every prompt token was installed once
    column = 2 * 1 * 2 * 8 * 4      # K and V x [L, H, Dh] fp32
    decoded = sum(len(o) - len(p) - 1 for o, p in zip(outs, prompts))
    assert moved["kv.append_bytes"] == column * (
        decoded + sum(len(p) for p in prompts))
    text = prometheus_text()
    for n in names:
        assert "serving_" + n.replace(".", "_") + "_total" in text


def test_generate_and_its_prefill_share_a_req(tmp_path, capsys):
    """`server/generate` on the handler's thread and `engine/prefill` on
    the engine's carry the same `req`; every engine span has its parent on
    the engine's thread."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_smoke
    import paddle_tpu.dygraph as dg
    from paddle_tpu import profiler as prof
    from paddle_tpu.inference.server import InferenceServer
    serve_smoke.save_tiny_model(str(tmp_path))
    with dg.guard():
        with _seeded(99):
            gen = _tiny_gpt()
        gen.eval()
        srv = InferenceServer(str(tmp_path), generator=gen, gen_slots=2)
        srv.start()
        prof.start_profiler(state="CPU")
        try:
            base = f"http://{srv.host}:{srv.port}"
            for ids in ([4, 9, 7], [[5, 6], [8, 3, 2, 11]]):
                _post(base + "/generate",
                      {"input_ids": ids, "max_length": 3})
        finally:
            prof.stop_profiler(profile_path=None)
            srv.stop()
    capsys.readouterr()
    events = list(prof._state.events)
    posts = [e for e in events if e.name == "server/generate"]
    assert [e.fields["n"] for e in posts] == [1, 2]
    assert len({e.fields["req"] for e in posts}) == 2
    prefills = [e for e in events if e.name == "engine/prefill"]
    assert sorted(e.fields["req"] for e in prefills) == sorted(
        [posts[0].fields["req"]] + 2 * [posts[1].fields["req"]])
    for e in prefills:
        assert e.fields["prompt"] in (2, 3, 4) and e.fields["bucket"] == 16
        assert e.fields["radix_hit"] == 0 and e.fields["waited_ms"] >= 0
        assert e.thread != posts[0].thread
    parents = {}
    for e in events:
        parents.setdefault(e.name, set()).add(e.parent)
    assert parents["server/wait"] == {"server/generate"}
    assert parents["engine/prefill"] == parents["engine/step"] == {None}
    assert parents["engine/idle"] == parents["engine/admit"] == {None}
    for child in ("engine/build", "engine/kv_install"):
        assert parents[child] == {"engine/prefill"}
    for child in ("engine/gather", "engine/kv_append"):
        assert parents[child] == {"engine/step"}
    for child in ("engine/upload", "engine/forward", "engine/fetch",
                  "engine/sample"):
        assert parents[child] == {"engine/prefill", "engine/step"}
    assert parents["engine/finish"] <= {"engine/prefill", "engine/step"}
    steps = [e for e in events if e.name == "engine/step"]
    assert all(1 <= e.fields["active"] <= 2 and e.fields["lpad"] == 16
               for e in steps)
    moved = [e for e in events if e.name in (
        "engine/upload", "engine/fetch", "engine/gather",
        "engine/kv_install", "engine/kv_append")]
    assert moved and all(e.fields["bytes"] > 0 for e in moved)
