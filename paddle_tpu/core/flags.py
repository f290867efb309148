"""Global flag system — the gflags analog.

Reference: /root/reference/paddle/fluid/platform/flags.cc (32 DEFINEs),
pybind/global_value_getter_setter.cc (runtime get/set), and the Python
bootstrap fluid/__init__.py __bootstrap__ (whitelisted FLAGS_* env vars).

TPU note: memory-fraction / cudnn / NCCL knobs have no XLA meaning but are
REGISTERED (with their reference defaults) so user scripts that set them
keep working; behavioural flags (check_nan_inf, eager_run, seed,
use_flash_attention) are read by the runtime.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

__all__ = ["get_flags", "set_flags", "define_flag", "flag"]

_lock = threading.Lock()
_FLAGS: Dict[str, Any] = {}
_DEFS: Dict[str, Any] = {}


def define_flag(name: str, default, help_str: str = ""):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    _DEFS[name] = (default, help_str)
    env = os.environ.get(name)
    if env is not None:
        _FLAGS[name] = _coerce(env, default)
    else:
        _FLAGS[name] = default


def _coerce(value, like):
    if isinstance(like, bool):
        return str(value).lower() in ("1", "true", "yes", "on")
    if isinstance(like, int):
        return int(value)
    if isinstance(like, float):
        return float(value)
    return value


def get_flags(flags):
    """paddle.get_flags parity: str or list → {name: value}."""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f if f.startswith("FLAGS_") else "FLAGS_" + f
        if key not in _FLAGS:
            raise ValueError(f"unknown flag {f!r}")
        out[f] = _FLAGS[key]
    return out


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags parity."""
    with _lock:
        for f, v in flags.items():
            key = f if f.startswith("FLAGS_") else "FLAGS_" + f
            if key not in _FLAGS:
                raise ValueError(f"unknown flag {f!r}")
            default = _DEFS[key][0]
            _FLAGS[key] = _coerce(v, default) \
                if default is not None else v


def flag(name: str, default=None):
    """Fast internal read (env fallback for flags set before import)."""
    key = name if name.startswith("FLAGS_") else "FLAGS_" + name
    if key in _FLAGS:
        return _FLAGS[key]
    env = os.environ.get(key)
    if env is not None and default is not None:
        return _coerce(env, default)
    return default


# ---------------------------------------------------------------------------
# registered flags (platform/flags.cc parity + TPU-native behavioural flags)
# ---------------------------------------------------------------------------
# behavioural (consumed by this framework)
define_flag("check_nan_inf", False,
            "scan fetches/state for NaN/Inf each step (flags.cc:44)")
define_flag("eager_run", False,
            "interpret programs op-by-op instead of whole-graph jit")
define_flag("tensor_array_max_len", 256,
            "default TensorArray capacity (static-shape buffer bound)")
define_flag("use_flash_attention", False,
            "route attention through the Pallas flash kernel")
define_flag("benchmark", False, "sync + time every executor run")
define_flag("dataset_chunk_steps", 1,
            "train_from_dataset: batch this many consecutive same-shape "
            "steps into one scanned device dispatch (Executor.run_steps)")
define_flag("dataset_prefetch_depth", 2,
            "train_from_dataset: async device-placement read-ahead depth "
            "(reader.Prefetcher); 0 disables the placement stage")
define_flag("feed_bucketing", "existing",
            "executor batch-dim bucketing on a step-cache miss: 'existing' "
            "pads ragged batches up to an already-compiled larger batch, "
            "'pow2' also cold-compiles at power-of-two buckets "
            "(inference), 'off' disables")
define_flag("recompute", "",
            "activation checkpointing in append_backward: '' = off, "
            "'auto' = select transformer-layer checkpoints and rewrite "
            "only when the HBM estimator predicts PADDLE_TPU_HBM_BYTES "
            "is exceeded, 'always' = rewrite unconditionally; explicit "
            "checkpoints= lists always win (static/memory_analysis.py)")
define_flag("hbm_dp_shard", 0,
            "HBM accounting: assume ZeRO-1 optimizer-state sharding over "
            "this many dp replicas (distributed/sharding.py) — the "
            "auto-remat verdict's optimizer-slot reservation and "
            "analyze_program's prediction mode divide slot bytes by it")
define_flag("hbm_zero_stage", 0,
            "HBM accounting: ZeRO stage the FLAGS_hbm_dp_shard "
            "prediction assumes (1 = slots only, 3 also divides the "
            "parameters the pass would pack; 0 defaults to 1)")
define_flag("hbm_assume_batch", 0,
            "batch size the HBM estimator binds symbolic -1 dims to "
            "(memory_analysis; 0 binds 1, making batch-dynamic "
            "estimates a lower bound)")
define_flag("sort_sum_gradient", False,
            "deterministic gradient accumulation order (flags.cc:521)")
define_flag("check_unused_vars", False,
            "warn on program vars no op consumes")

# accepted-for-parity (no XLA meaning; reference defaults)
define_flag("fraction_of_gpu_memory_to_use", 0.92, "flags.cc:407 (no-op)")
define_flag("initial_gpu_memory_in_mb", 0, "no-op")
define_flag("reallocate_gpu_memory_in_mb", 0, "no-op")
define_flag("allocator_strategy", "auto_growth", "no-op (XLA allocator)")
define_flag("cudnn_deterministic", False, "XLA is deterministic per build")
define_flag("cudnn_exhaustive_search", False, "no-op")
define_flag("sync_nccl_allreduce", True, "no-op (XLA schedules)")
define_flag("nccl_nrings", 1, "no-op")
define_flag("eager_delete_tensor_gb", 0.0, "no-op (XLA buffer liveness)")
define_flag("fast_eager_deletion_mode", True, "no-op")
define_flag("memory_fraction_of_eager_deletion", 1.0, "no-op")
define_flag("use_pinned_memory", True, "no-op")
define_flag("use_mkldnn", False, "no-op")
define_flag("rpc_deadline", 180000, "PS rpc timeout ms")
define_flag("selected_xlas", "", "device ordinal list (launcher contract)")
define_flag("selected_gpus", "", "alias of selected_xlas")
