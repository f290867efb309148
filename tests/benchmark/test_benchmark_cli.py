"""`benchmark/run.py` as the driver starts it: it refuses anything but a
TPU, and a checkout that holds only the benchmark, without a result line."""
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "bert-base.pretrain_s512", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _no_result_line(stdout):
    return not any(ln.startswith("{") and '"metrics"' in ln
                   for ln in stdout.splitlines())


def test_cli_refuses_anything_but_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py")] + ARGS,
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO)
    assert time.monotonic() - t0 < 10.0
    assert proc.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in proc.stderr
    assert "platform=cpu" in proc.stdout and _no_result_line(proc.stdout)
    assert "train:" not in proc.stdout


def test_cli_refuses_a_checkout_that_holds_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py")] + ARGS,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120, cwd=tmp_path)
    assert proc.returncode != 0
    assert "paddle_tpu/) is not in this checkout" in proc.stderr
    assert _no_result_line(proc.stdout)


def test_cli_rejects_unknown_workloads_and_missing_arguments():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = [sys.executable, os.path.join(REPO, "benchmark", "run.py")]
    proc = subprocess.run(run + ["--workload", "no.such", "--seed", "1",
                                 "--seconds", "1", "--trace", "0"],
                          env=env, capture_output=True, text=True,
                          timeout=120, cwd=REPO)
    assert proc.returncode != 0 and "no workload 'no.such'" in proc.stderr
    assert _no_result_line(proc.stdout)
    proc = subprocess.run(run + ["--workload", "bert-base.pretrain_s512"],
                          env=env, capture_output=True, text=True,
                          timeout=120, cwd=REPO)
    assert proc.returncode == 2 and _no_result_line(proc.stdout)
