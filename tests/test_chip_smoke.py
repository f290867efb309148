"""chip_smoke.py rehearsed on the CPU: every phase function at a tiny size
(the Pallas kernels interpreted, because the backend is `cpu`), and the
script's own entry refusing to run anywhere but a TPU."""
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_train_then_scanned_window():
    run = chip_smoke.phase_train(vocab=256, seq=16, hidden=32, layers_n=1,
                                 heads=2, batch=4)
    assert len(run.losses) == 5 and run.losses[-1] < run.losses[0]
    scanned = chip_smoke.phase_train_scanned(run, k=4)
    assert scanned.shape == (4,)


def test_kernels_interpreted_on_cpu():
    report = chip_smoke.phase_kernels((1, 2, 64, 16))
    assert set(report) == {"flash_causal_False", "flash_causal_True"}


def test_serve_over_http():
    out = chip_smoke.phase_serve(
        dict(vocab_size=64, hidden_size=16, num_layers=2, num_heads=2,
             max_position=32),
        n_requests=2, prompt_tokens=5, new_tokens=3, page_tokens=4,
        pool_extra_bytes=1 << 20, request_timeout_s=120.0)
    assert out["token_equal"] == out["requests"] == 2


def test_cli_refuses_anything_but_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=60)
    assert time.monotonic() - t0 < 10.0
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr
    assert "platform=cpu" in proc.stdout
    # refused before any model was built, and printed no result
    assert "train:" not in proc.stdout and '"ok"' not in proc.stdout


def test_launcher_parent_stays_off_jax():
    """One process for each chip: the launcher parent must not initialise
    a JAX backend, or the trainers it spawns cannot have the device."""
    code = ("import paddle_tpu.distributed.launch, "
            "paddle_tpu.distributed.launch_utils, jax._src.xla_bridge as xb;"
            " raise SystemExit(1 if xb._backends else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          env=dict(os.environ, PYTHONPATH=REPO),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
