"""Executor: runs a Program by tracing its whole block into ONE jitted XLA
computation.

Analog of the reference executor stack
(/root/reference/python/paddle/fluid/executor.py:474 Executor,
 /root/reference/paddle/fluid/framework/executor.cc:474-480 per-op hot loop) —
but where the reference interprets op-by-op with per-kernel launches, here the
op list is composed into a single function (state, feed, seed) ->
(fetches, state') and `jax.jit`-ed with state buffers donated, so XLA fuses
the entire step (SURVEY.md §3.1 "the whole :474-480 loop becomes ONE traced
XLA computation").  Garbage collection (executor.cc:445-472 GC selection)
disappears: XLA buffer liveness subsumes it.

Startup programs are interpreted eagerly op-by-op — they run once, tracing
would only add compile latency.  Set FLAGS_eager_run=1 to interpret main
programs too (debug path, analog of the reference's sequential executor).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.program import Program, Block, default_main_program, OpRole
from ..core.place import CPUPlace, XLAPlace, Place, _current_expected_place
from ..core.dtype import np_dtype
from ..core import compile_cache as _ccache
from ..ops.registry import get_op_info, OpContext
from ..profiler import NO_PHASE, Phase, RecordEvent
from ..testing import chaos as _chaos

__all__ = ["Executor", "Scope", "global_scope", "scope_guard",
           "as_numpy", "BlockTracer"]


class Scope:
    """name -> device array store (analog of framework/scope.h:52, flattened:
    no parent chain — programs here use unique names)."""

    def __init__(self):
        self.vars: Dict[str, Any] = {}

    def find_var(self, name: str):
        return _VarView(self, name) if name in self.vars else None

    def var(self, name: str):
        self.vars.setdefault(name, None)
        return _VarView(self, name)

    def set(self, name: str, value):
        self.vars[name] = value

    def get(self, name: str):
        return self.vars.get(name)

    def drop_kids(self):
        pass

    def keys(self):
        return self.vars.keys()


class _VarView:
    def __init__(self, scope, name):
        self._scope, self._name = scope, name

    def get_tensor(self):
        return self._scope.vars[self._name]

    def set(self, value, place=None):
        self._scope.vars[self._name] = jnp.asarray(value)


_global_scope = Scope()
_scope_stack = threading.local()


def global_scope() -> Scope:
    stack = getattr(_scope_stack, "stack", None)
    return stack[-1] if stack else _global_scope


class scope_guard:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        if not hasattr(_scope_stack, "stack"):
            _scope_stack.stack = []
        _scope_stack.stack.append(self.scope)
        return self.scope

    def __exit__(self, *a):
        _scope_stack.stack.pop()


def as_numpy(x):
    if isinstance(x, (list, tuple)):
        return [as_numpy(i) for i in x]
    return np.asarray(x)


# ---------------------------------------------------------------------------
# block tracing
# ---------------------------------------------------------------------------
class BlockTracer:
    """Composes a block's ops into one pure function over an environment of
    jax values.  Shared by Executor (jit path), the startup interpreter, and
    the distributed CompiledProgram (which traces under shard_map)."""

    def __init__(self, block: Block, skip_types=("feed", "fetch")):
        self.block = block
        self.skip_types = set(skip_types)

    def run(self, env: Dict[str, Any], ctx: OpContext,
            ops=None, on_op=None) -> Dict[str, Any]:
        for op in (ops if ops is not None else self.block.ops):
            if op.type in self.skip_types:
                continue
            self.run_op(op, env, ctx)
            if on_op is not None:
                on_op(op, env)
        return env

    def run_op(self, op, env: Dict[str, Any], ctx: OpContext):
        # sub-block ops (while/cond/static_rnn/...) reach their Program
        # through the context and recurse with their own BlockTracer
        ctx.program = self.block.program
        info = get_op_info(op.type)
        if info is None:
            raise NotImplementedError(
                f"op {op.type!r} has no registered kernel")
        ins: Dict[str, Any] = {}
        for slot in info.inputs:
            names = op.inputs.get(slot.name, [])
            if slot.duplicable:
                if slot.name.endswith("@GRAD"):
                    # cotangent lists must stay POSITION-ALIGNED with the
                    # forward output slot — an absent grad ('' name, e.g.
                    # a while's non-differentiable carried cond) is None,
                    # not dropped, or every grad after it lands on the
                    # wrong output
                    ins[slot.name] = [env.get(n) if n else None
                                      for n in names]
                else:
                    ins[slot.name] = [env[n] for n in names
                                      if n and n in env]
            else:
                n = names[0] if names else None
                ins[slot.name] = env.get(n) if n else None
        attrs = dict(op.attrs)
        # the device trace's name for what the PROGRAM asked for: XLA
        # keeps this path as every lowered instruction's op_name, so the
        # profiler's `tf_op` reads jit(step)/backward/mul_grad/...  Paid
        # while tracing only, never per step.
        with jax.named_scope(_device_scope(op)):
            outs = info.kernel(ins, attrs, ctx)
        for slot in info.outputs:
            names = op.outputs.get(slot.name, [])
            if not names:
                continue
            val = outs.get(slot.name) if outs else None
            if val is None:
                continue
            if slot.duplicable:
                for n, v in zip(names, val):
                    if n and v is not None:
                        env[n] = v
            else:
                if names[0]:
                    env[names[0]] = val
        return env


def _device_scope(op) -> str:
    """`<role>/<op type>` from the op's IR role.  `op_role` is bit flags:
    Loss is or-ed onto Forward / Backward and names neither, so it only
    decides between the two; LRSched (alone or or-ed onto Optimize) and
    RPC / Dist keep their own names."""
    role = int(op.attrs.get(OpRole.KEY, OpRole.Forward))
    if role & OpRole.LRSched:
        name = "lr_sched"
    else:
        name = {OpRole.Forward: "forward", OpRole.Backward: "backward",
                OpRole.Optimize: "optimize", OpRole.RPC: "rpc",
                OpRole.Dist: "dist"}.get(role & ~OpRole.Loss, "forward")
    return f"{name}/{op.type}"


def _fetch_value(env: Dict[str, Any], name: str, program: Program):
    """A fetch target's value, or an error that says why no op of the
    program produces it (a rewrite records what it fused away)."""
    if name in env:
        return env[name]
    why = getattr(program, "_fused_away", {}).get(name)
    raise KeyError(
        f"fetch target {name!r} is not produced by this program"
        + (f": {why}" if why else ""))


def _persistable_names(program: Program) -> List[str]:
    return sorted(v.name for b in program.blocks for v in b.vars.values()
                  if v.persistable)


def _unwrap_program(program):
    """Peel executable wrappers down to the underlying Program:
    ParallelExecutor wraps a CompiledProgram (``._compiled``) which wraps
    the Program (``._program``) — the checkpoint hook must reach the real
    Program through either."""
    for _ in range(4):
        if program is None or isinstance(program, Program):
            break
        inner = getattr(program, "_program", None)
        if inner is None:
            inner = getattr(program, "_compiled", None)
        if inner is None:
            break
        program = inner
    return program


def _wrapper_chips(program) -> int:
    """Device count of an executable wrapper's (already-built) mesh —
    the MFU denominator must scale with the chips that shared the step.
    Falls back to 1 when no mesh is discoverable."""
    for obj in (program, getattr(program, "_compiled", None)):
        mesh = getattr(obj, "_mesh", None) if obj is not None else None
        if mesh is not None:
            try:
                return max(1, int(len(mesh.devices.flat)))
            except Exception:
                pass
    return 1


_OPTIMIZER_OP_TYPES = frozenset(
    ("sgd", "momentum", "adam", "adamw", "adagrad", "rmsprop", "lamb",
     "lars_momentum", "dgc_momentum", "ftrl", "adamax", "adadelta"))


def _is_training(program: Program) -> bool:
    """A program that updates state: has backward or optimizer ops.
    Distinguishes the real train program from startup (pure initializers)
    and eval programs when the checkpoint hook has to bind by itself."""
    return any(op.type.endswith("_grad") or op.type in _OPTIMIZER_OP_TYPES
               for b in program.blocks for op in b.ops)


class _CkptHook:
    """Periodic-checkpoint registration (enable_checkpointing).

    `program` may start as None and is latched by _maybe_checkpoint onto
    the first training program run afterwards; `run_scope` tracks the
    scope that program last ran in (for the preemption provider when no
    scope was given at enable time); `last` is the executor step of the
    most recent save (re-anchored by restore)."""

    __slots__ = ("manager", "program", "every", "scope", "last",
                 "run_scope")

    def __init__(self, manager, program, every, scope, last):
        self.manager = manager
        self.program = program
        self.every = every
        self.scope = scope
        self.last = last
        self.run_scope = None


class Executor:
    """exe = Executor(XLAPlace(0)); exe.run(startup); exe.run(main, feed,
    fetch_list) — the reference's two-program contract (executor.py:474)."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place or _current_expected_place()
        # persistent on-disk XLA cache (core/compile_cache.py): a process
        # restart re-loads serialized executables instead of re-compiling
        _ccache.initialize()
        # compiled step cache: key -> (jitted fn, state names)
        self._cache: Dict[Tuple, Any] = {}
        # miss-key -> (bucket key, padded batch) memo so a recurring ragged
        # batch pays the bucket search once, not every step
        self._bucket_map: Dict[Tuple, Tuple] = {}
        # feed bucketing policy: "existing" pads a cache-missing ragged
        # batch up to the smallest already-compiled batch (training: the
        # epoch's last partial batch reuses the steady-state executable);
        # "pow2" additionally cold-compiles at the next power-of-two
        # bucket (variable-length inference: total traces bounded at
        # log2(max batch)); "off" disables padding.
        from ..core.flags import flag
        self.bucket_policy = flag("feed_bucketing", "existing")
        self._stats = {"hits": 0, "misses": 0, "traces": 0,
                       "bucket_hits": 0}
        # cache entries (this executor's and its CompiledPrograms') whose
        # launches may still obtain an executable: key -> the fields of
        # their `executor/first_launch` phase
        self._unsettled = {}
        self._step = 0
        # chaos fault-injection step index (testing/chaos.py): counts
        # TRAINING run()/run_steps() calls only (_chaos_step gates on
        # _is_training, so startup/eval runs never shift the spec) —
        # kill@<n> means "after the n-th train step"
        self._train_runs = 0
        # elastic micro-step count (distributed/elastic.py): unlike
        # _step, this counts ONLY elastic CompiledProgram runs (startup/
        # eval runs pollute _step), so global step = _elastic_steps // K
        # is exact and survives topology-shifted restores
        self._elastic_steps = 0
        # periodic checkpointing (enable_checkpointing): (manager,
        # program, every_n_steps, scope, last-saved-step)
        self._ckpt = None
        self._ckpt_barrier = None
        self._active_prefetcher = None
        self.last_restored_extra = None  # sidecar of the last resume
        # telemetry (docs/observability.md): chip peak FLOPs/s resolved
        # once per executor (None = not yet; 0.0 = unknown -> no MFU)
        self._peak_flops = None
        self._observed_steps = 0
        # (program, process trace count, time) of the last observation
        self._last_observed = None

    # -- public API ---------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, fetch_var_name="fetch",
            feed_var_name="feed", use_prune=False):
        from ..distributed.compiled_program import CompiledProgram
        if isinstance(program, CompiledProgram) or (
                program is not None and not isinstance(program, Program)
                and hasattr(program, "_run")):
            # CompiledProgram / Pipeline / PS trainer program dispatch.
            # The checkpoint hook still fires: multi-chip pretraining is
            # the workload the checkpoint tier exists for.
            with RecordEvent("Executor::Run"):
                results = program._run(self, feed, fetch_list, scope,
                                       return_numpy)
                with RecordEvent("executor/observe"):
                    self._observe_step(program, feed or {},
                                       chips=_wrapper_chips(program))
                # resolve the scope the wrapper actually ran in: some
                # wrappers (ParallelExecutor) carry their own _scope —
                # snapshotting global_scope() instead would commit an
                # EMPTY checkpoint
                with RecordEvent("executor/hooks"):
                    self._maybe_checkpoint(
                        program, scope or getattr(program, "_scope", None)
                        or global_scope())
                    self._chaos_step(program)
            return results
        if getattr(program, "_ps_server_config", None):
            # pserver program: exe.run(pserver_prog) == listen_and_serv
            from ..distributed.ps.kv_server import KVServer
            cfg = program._ps_server_config
            server = KVServer(cfg["endpoint"],
                              num_trainers=cfg.get("num_trainers", 1))
            server.serve()  # blocks until a SHUTDOWN rpc
            return []
        program = program if program is not None else default_main_program()
        scope = scope or global_scope()
        feed = feed or {}
        fetch_names = [f.name if hasattr(f, "name") else str(f)
                       for f in (fetch_list or [])]

        if self._program_is_startup(program):
            # once a model: a static model's weights come from the seed here
            with Phase("executor/first_launch", mode="startup",
                       fingerprint=str(program.fingerprint())[:16],
                       startup=1):
                self._run_eager(program, scope, feed, fetch_names)
            return [] if not fetch_names else [
                as_numpy(scope.get(n)) if return_numpy else scope.get(n)
                for n in fetch_names]

        from ..core.flags import flag
        from ..core.monitor import stat_add
        from ..incubate.checkpoint.auto_checkpoint import _auto_checkpoint
        with RecordEvent("Executor::Run"):
            # elastic auto-checkpoint hook (reference executor.py:1194)
            with RecordEvent("executor/hooks"):
                _auto_checkpoint(self, program)
            stat_add("executor_run_times")
            if flag("eager_run", False):
                self._run_eager(program, scope, feed, fetch_names)
                fetched = [scope.get(n) for n in fetch_names]
                results = [as_numpy(f) for f in fetched] \
                    if return_numpy else fetched
            else:
                results = self._run_compiled(program, scope, feed,
                                             fetch_names, return_numpy)
            with RecordEvent("executor/observe"):
                self._observe_step(program, feed)
            with RecordEvent("executor/hooks"):
                if flag("check_nan_inf", False):
                    self._check_nan_inf(fetch_names, results, scope,
                                        program=program)
                self._maybe_checkpoint(program, scope)
                self._chaos_step(program)
        return results

    def _chaos_step(self, program):
        """Count this run toward the chaos step index ONLY when it was a
        TRAINING run: the PADDLE_TPU_CHAOS contract is 'after the n-th
        train step', and an interleaved eval/test-program run must not
        shift the injected-fault positions.  Training-ness is cached on
        the (unwrapped) program; everything here is skipped when chaos
        is off."""
        if not _chaos.enabled():
            return
        p = _unwrap_program(program)
        cached = getattr(p, "_chaos_is_training", None)
        if cached is None:
            cached = isinstance(p, Program) and _is_training(p)
            try:
                p._chaos_is_training = cached
            except (AttributeError, TypeError):  # exotic wrapper
                pass
        if cached:
            self._train_runs += 1
            _chaos.step_hook(self._train_runs)

    # -- step telemetry (docs/observability.md) -----------------------------
    @staticmethod
    def _is_training_cached(p) -> bool:
        cached = getattr(p, "_telemetry_is_training", None)
        if cached is None:
            cached = isinstance(p, Program) and _is_training(p)
            try:
                p._telemetry_is_training = cached
            except (AttributeError, TypeError):
                pass
        return cached

    @staticmethod
    def _feed_tokens(feed_vals, stacked: bool) -> int:
        """Tokens processed by one dispatch, inferred from the feed: the
        largest >=2-D integer feed's numel (ids-style models — the
        labels feed ties, max() is stable); else batch rows (x-style
        models).  `stacked` marks run_steps feeds ([K, B, ...]: rows
        are the two leading dims)."""
        best_int = 0
        rows = 0
        for v in feed_vals.values():
            shape = tuple(getattr(v, "shape", ()) or ())
            if not shape:
                continue
            dt = getattr(v, "dtype", None)
            try:
                kind = np.dtype(str(dt)).kind if dt is not None else "?"
            except TypeError:  # framework dtype numpy can't parse
                kind = "?"
            if kind in ("i", "u") and len(shape) >= 2:
                n = 1
                for d in shape:
                    n *= int(d)
                best_int = max(best_int, n)
            lead = int(shape[0])
            if stacked and len(shape) >= 2:
                lead *= int(shape[1])
            rows = max(rows, lead)
        return best_int or rows

    @staticmethod
    def _feed_batch(feed_vals, stacked: bool) -> int:
        """Per-step batch from the feed's leading dims (the -1 binding
        for the cached FLOPs/HBM walks); `stacked` = run_steps feeds
        whose per-step batch is axis 1.  The MOST COMMON candidate wins
        (ties -> largest) so a lone non-batch feed — a fed lr of shape
        [1], a lookup table — cannot poison the per-program cache."""
        counts: Dict[int, int] = {}
        for v in feed_vals.values():
            shape = tuple(getattr(v, "shape", ()) or ())
            if len(shape) >= (2 if stacked else 1):
                b = int(shape[1] if stacked else shape[0])
                counts[b] = counts.get(b, 0) + 1
        if not counts:
            return 0
        return max(counts, key=lambda b: (counts[b], b))

    def _flops_per_step(self, p, batch) -> Optional[int]:
        """analyze_flops total for this program at `batch`, cached on
        the program (one IR walk per distinct batch, then a dict hit)."""
        try:
            cache = p.__dict__.setdefault("_flops_by_batch", {})
        except (AttributeError, TypeError):
            return None
        if batch not in cache:
            try:
                from .flops_analysis import analyze_flops
                cache[batch] = analyze_flops(p, batch=batch)[
                    "total_flops"]
            except Exception:
                cache[batch] = None  # telemetry never kills training
        return cache[batch]

    def _observe_step(self, program, feed_vals, steps=1, chips=1,
                      stacked=None):
        """Per-train-step telemetry: wall time, tokens/s, achieved-vs-
        peak MFU, retrace count into core/monitor; one journal event;
        one heartbeat.  Costs a handful of registry writes when nothing
        is armed; skipped entirely for non-training programs (startup /
        eval).  Fully fenced: telemetry must never kill a training run,
        so ANY failure here (unparseable feed dtype, a user-registered
        metric-name collision, a sick disk under the journal) degrades
        to a silently skipped observation.

        The step's time is the interval since the previous observation
        of the same program.  The time a dispatch takes to RETURN is only
        the enqueue when fetches stay on the device (`return_numpy=
        False`: every real training loop), while in steady state the
        interval between dispatches is the step either way.  There is
        none (`dt` None: counts and liveness only) for a program's first
        observation and for the one after a trace, which holds the
        compile."""
        p = _unwrap_program(program)
        if not self._is_training_cached(p):
            return
        import time as _time
        from ..core.monitor import stat_get
        now = _time.perf_counter()
        traces = stat_get(_ccache.STAT_TRACES)
        last, self._last_observed = self._last_observed, (p, traces, now)
        dt = now - last[2] if last is not None and last[0] is p \
            and last[1] == traces else None
        try:
            self._observe_step_inner(
                p, dt, feed_vals, steps, chips,
                steps > 1 if stacked is None else stacked)
        except Exception:
            pass

    def _observe_step_inner(self, p, dt, feed_vals, steps, chips,
                            stacked):
        from ..core.monitor import gauge_set, hist_observe, stat_add
        from ..observability import heartbeat as _hb
        from ..observability import journal as _journal
        from ..observability.sidecar import maybe_start_from_env
        maybe_start_from_env()
        self._observed_steps += steps
        stat_add("train.steps", steps)
        gauge_set("executor.retraces", self._stats["traces"])
        timed = {}      # what only an interval gives: journal + heartbeat
        if dt:
            step_ms = dt * 1e3 / max(1, steps)
            hist_observe("train.step_ms", step_ms)
            timed["wall_ms"] = round(step_ms, 3)
        tokens = self._feed_tokens(feed_vals, stacked=stacked)
        tps = None
        if tokens and dt:
            tps = tokens / dt
            gauge_set("train.tokens_per_sec", tps)
        mfu = None
        if self._peak_flops is None:
            from .flops_analysis import peak_flops_per_chip
            try:
                self._peak_flops = float(peak_flops_per_chip())
            except ValueError:  # a device_kind with no recorded peak
                self._peak_flops = 0.0
        if self._peak_flops and dt:
            batch = self._feed_batch(feed_vals, stacked=stacked)
            flops = self._flops_per_step(p, batch) if batch else None
            if flops:
                mfu = (flops * steps) / dt / (self._peak_flops
                                              * max(1, chips))
                gauge_set("train.mfu", mfu)
        # predicted-vs-ground-truth HBM: the estimate once per program,
        # the allocator's answer every 64 steps (a C call, not free)
        if self._observed_steps == steps or \
                self._observed_steps % 64 < steps:
            self._observe_hbm(p, feed_vals, stacked)
        _hb.maybe_beat(self._step, **timed)
        if _journal.journal_enabled():
            ev = dict(timed, step=self._step)
            if steps > 1:
                ev["micro_steps"] = steps
            if tps is not None:
                ev["tokens_per_sec"] = round(tps, 1)
            if mfu is not None:
                ev["mfu"] = round(mfu, 5)
            _journal.emit("step", **ev)

    def _observe_hbm(self, p, feed_vals, stacked):
        from ..core.monitor import gauge_set
        try:
            batch = self._feed_batch(feed_vals, stacked=stacked)
            cache = p.__dict__.setdefault("_hbm_by_batch", {})
            if batch and batch not in cache:
                from .memory_analysis import analyze_program
                cache[batch] = analyze_program(p, batch=batch)[
                    "peak_bytes"]
            if batch and cache.get(batch):
                gauge_set("hbm.predicted_peak_bytes", cache[batch])
            import jax as _jax
            stats = _jax.local_devices()[0].memory_stats() or {}
            peak = stats.get("peak_bytes_in_use")
            if peak:
                gauge_set("hbm.device_peak_bytes", int(peak))
        except Exception:
            pass  # backends without memory_stats / exotic programs

    def _check_nan_inf(self, fetch_names, results, scope, program=None,
                       steps=1):
        """FLAGS_check_nan_inf (reference details/nan_inf_utils_detail —
        per-op output scan; here: fetches + persistable state after the
        jitted step, which bounds the same failure).

        Each finding names the PRODUCING op (type, op_uid, op index) and
        the value's dtype, resolved from `program`'s IR — not just the
        fetch name — so a NaN points at the kernel that minted it, like
        the reference's CheckOpHasNanOrInf.  Under ``run_steps`` (where
        fetches are stacked ``[K, ...]``) the report also names the
        first micro-step whose slice went non-finite.  Works identically
        for run() and run_steps(); the eager path has the sharper
        `_per_op_nan_scan`.  (docs/static_analysis.md "NaN/Inf
        debugging".)"""
        bad = []  # (kind, name, array, step_idx or None)
        for n, v in zip(fetch_names, results or []):
            arr = np.asarray(v)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                step_idx = None
                if steps > 1 and arr.ndim >= 1 and arr.shape[0] == steps:
                    per_step = np.isfinite(
                        arr.reshape(steps, -1)).all(axis=1)
                    step_idx = int(np.argmin(per_step))
                bad.append(("fetch", n, arr, step_idx))
        scan_names = _persistable_names(program) if program is not None \
            else list(scope.keys())
        for n in scan_names:
            v = scope.get(n)
            if v is None:
                continue
            arr = np.asarray(v)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                bad.append(("var", n, arr, None))
        if not bad:
            return
        producers = {}
        if program is not None:
            for b in program.blocks:
                for i, op in enumerate(b.ops):
                    for out_name in op.output_names():
                        if out_name:
                            # keep the LAST writer: that is the value the
                            # step actually committed
                            producers[out_name] = (b.idx, i, op)
        msgs = []
        for kind, n, arr, step_idx in bad:
            msg = f"{kind} {n!r} (dtype {arr.dtype})"
            if step_idx is not None:
                msg += f", first non-finite at micro-step {step_idx}"
            hit = producers.get(n)
            if hit is not None:
                bi, oi, op = hit
                msg += (f", produced by op {op.type!r} "
                        f"(uid {op.attrs.get('op_uid')}, "
                        f"block {bi} op {oi})")
            msgs.append(msg)
        raise RuntimeError(
            "FLAGS_check_nan_inf: non-finite values in "
            + "; ".join(msgs))

    # -- dataset-driven training (MultiTrainer path, executor.py:1345) ------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        from ..distributed.dataset import run_from_dataset
        from ..core.program import default_main_program
        if fetch_handler is not None:
            raise NotImplementedError(
                "fetch_handler callbacks are not supported; poll "
                "fetch_list/print_period instead")
        program = program if program is not None else default_main_program()
        if thread:
            dataset.set_thread(thread)
        return run_from_dataset(self, program, dataset, scope,
                                fetch_list, fetch_info, print_period, debug)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """Like train_from_dataset but guaranteed side-effect-free on the
        parameters (reference executor.py:1476 contract): training-role
        ops (backward/optimizer/lr-sched) are stripped and test mode is
        applied before running."""
        from ..core.program import default_main_program, OpRole
        program = program if program is not None else default_main_program()
        infer = program.clone(for_test=True)
        blk = infer.global_block()
        train_roles = (OpRole.Backward, OpRole.Optimize, OpRole.LRSched,
                       OpRole.Optimize | OpRole.LRSched)
        blk.ops = [op for op in blk.ops
                   if op.attrs.get(OpRole.KEY, OpRole.Forward)
                   not in train_roles]
        return self.train_from_dataset(infer, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    @staticmethod
    def _per_op_nan_scan(op, env):
        """Eager-mode per-op output scan under FLAGS_check_nan_inf — names
        the op that produced the first non-finite value (reference
        details/nan_inf_utils_detail.cc CheckOpHasNanOrInf)."""
        for n in op.output_names():
            v = env.get(n)
            if v is None or not hasattr(v, "dtype"):
                continue
            if jnp.issubdtype(v.dtype, jnp.floating) and \
                    not bool(jnp.isfinite(v).all()):
                raise RuntimeError(
                    f"FLAGS_check_nan_inf: op {op.type!r} produced "
                    f"non-finite values in output {n!r}")

    def close(self):
        """Release the in-process jitted-step cache.  Idempotent — safe to
        call repeatedly (reference executor.py:658 close contract).  The
        persistent on-disk cache (core/compile_cache.py) is deliberately
        untouched: it is process-shared state, and the whole point is that
        the NEXT process starts hot.  Counters survive close so post-hoc
        `cache_stats()` still reports the session."""
        self._cache.clear()
        self._bucket_map.clear()
        self._unsettled.clear()

    # -- eager interpreter (startup / debug) --------------------------------
    def _program_is_startup(self, program: Program) -> bool:
        """Explicit two-program contract: program_guard / the default-program
        registry stamp `_role` ("startup" runs eagerly once, "main" takes the
        jit+donate path).  Unmarked programs (hand-built, deserialized) fall
        back to the init-op heuristic."""
        if program._role is not None:
            return program._role == "startup"
        b = program.global_block()
        init_types = {"fill_constant", "uniform_random", "gaussian_random",
                      "truncated_gaussian_random", "assign_value", "eye",
                      "c_broadcast", "broadcast", "seed", "range", "linspace"}
        return len(b.ops) > 0 and all(op.type in init_types for op in b.ops)

    def _run_eager(self, program: Program, scope: Scope, feed, fetch_names):
        from ..core.flags import flag
        block = program.global_block()
        env = {k: v for k, v in scope.vars.items() if v is not None}
        for name, val in feed.items():
            env[name] = self._coerce_feed(block, name, val)
        ctx = OpContext(seed=self._seed_for_step(program))
        on_op = self._per_op_nan_scan if flag("check_nan_inf", False) else None
        BlockTracer(block).run(env, ctx, on_op=on_op)
        self._step += 1
        # write back persistables + fetches
        for n in _persistable_names(program):
            if n in env:
                scope.set(n, env[n])
        for n in fetch_names:
            if n in env:
                scope.set(n, env[n])

    # -- compiled whole-block path ------------------------------------------
    def _run_compiled(self, program: Program, scope: Scope, feed,
                      fetch_names, return_numpy):
        block = program.global_block()
        with RecordEvent("executor/prepare"):
            feed_vals = {n: self._coerce_feed(block, n, v)
                         for n, v in feed.items()}
            state_names = [n for n in _persistable_names(program)
                           if scope.get(n) is not None]
            # signature from metadata only — np.asarray here would force
            # a blocking device->host copy of every feed on every step
            feed_sig = self._feed_signature(feed_vals)
            key = (program.fingerprint(), feed_sig, tuple(fetch_names),
                   tuple(state_names))
            fn = self._cache.get(key)
            bucket = None  # (real batch, padded batch)
            if fn is None:
                bucketed = self._bucket_lookup(key, feed_vals)
                if bucketed is not None:
                    key, feed_vals, bucket = bucketed
                    fn = self._cache.get(key)
        if fn is None:
            fingerprint = str(key[0])[:16]
            self._unsettled[key] = {"mode": "run",
                                    "fingerprint": fingerprint}
            with Phase("executor/trace_compile", mode="run",
                       fingerprint=fingerprint):
                # env-gated IR verification on the first compile of each
                # program (PADDLE_TPU_VERIFY — static/verifier.py): the
                # IR walk rides the already-slow trace path only
                from .verifier import verify_first_compile
                verify_first_compile(program, fetch_list=fetch_names)
                self._record("miss")
                self._record("trace")
                from ..observability.journal import emit as _jemit
                _jemit("compile", mode="run", fingerprint=fingerprint)
                fn = self._compile(program, state_names, fetch_names)
                self._cache[key] = fn
        else:
            self._record("hit", bucketed=bucket is not None)

        state = {n: scope.get(n) for n in state_names}
        first_launch = self._first_launch(key)
        with RecordEvent("executor/launch"), first_launch:
            seed = self._seed_for_step(program)
            fetches, new_state = fn(state, feed_vals, jnp.uint32(seed))
        self._settle(key, first_launch)
        self._step += 1
        for n, v in new_state.items():
            scope.set(n, v)
        if bucket is not None:
            fetches = self._unpad_fetches(fetches, *bucket,
                                          block=block,
                                          fetch_names=fetch_names)
        if return_numpy:
            with RecordEvent("executor/fetch"):
                return [np.asarray(f) for f in fetches]
        return list(fetches)

    # -- a new entry's first launches ------------------------------------------
    def _first_launch(self, key):
        """The kept phase `executor/first_launch` of a launch of cache entry
        `key` that may still obtain an executable — inside its
        `executor/launch` span, which stays where it is for hits and misses
        alike: a new entry's launches until one obtains none (`jax.jit` is
        lazy, so the first traces, lowers and compiles or loads; under a
        mesh the second does so again, for state that now lives sharded).
        `NO_PHASE` for every other: with everything warm, one test of an
        empty dict."""
        if not self._unsettled or key not in self._unsettled:
            return NO_PHASE
        return Phase("executor/first_launch", **self._unsettled[key],
                     startup=0)

    def _settle(self, key, first_launch):
        if first_launch is not NO_PHASE and \
                not first_launch.fields.get("executables"):
            del self._unsettled[key]

    # -- shape bucketing -----------------------------------------------------
    def _record(self, kind, bucketed=False):
        self._stats[kind + "es" if kind.endswith("s") else kind + "s"] += 1
        if kind == "hit":
            _ccache.record_hit(bucketed)
            if bucketed:
                self._stats["bucket_hits"] += 1
        elif kind == "miss":
            _ccache.record_miss()
        elif kind == "trace":
            _ccache.record_trace()

    @staticmethod
    def _common_leading_dim(feed_sig):
        """The shared batch dim of a feed signature, or None when feeds
        disagree / any feed is rank-0 (no well-defined batch axis)."""
        dims = set()
        for _, shape, _ in feed_sig:
            if not shape:
                return None
            dims.add(int(shape[0]))
        return dims.pop() if len(dims) == 1 else None

    def _bucket_lookup(self, miss_key, feed_vals):
        """On a step-cache miss, try to serve the step from a LARGER
        already-compiled batch bucket instead of tracing a fresh shape.

        Returns (bucket_key, padded_feed_vals, original_batch) or None.
        Policy "existing": pad up to the smallest compiled batch >= b with
        identical trailing dims/dtypes (epoch-tail ragged batch -> the
        steady-state executable).  Policy "pow2": when nothing compiled
        fits, target the next power-of-two >= b so variable-length
        inference settles into at most log2(max) buckets.  Padding
        repeats the final row — values stay in-domain (valid token ids,
        finite floats) and real rows' per-row fetches are bit-identical
        (row-independent programs); `_unpad_fetches` slices fetches back.
        Batch-reduced fetches (mean loss) and state updates DO see the
        duplicated rows — same tradeoff as pad-vs-drop-last in any
        static-shape pipeline (docs/perf.md)."""
        policy = self.bucket_policy
        if policy not in ("existing", "pow2") or not feed_vals:
            return None
        memo = self._bucket_map.get(miss_key)
        if memo is not None:
            bucket_key, target = memo
            return (bucket_key, self._pad_feeds(feed_vals, target), target)
        fp, feed_sig, fetch_names, state_names = miss_key
        b = self._common_leading_dim(feed_sig)
        if b is None:
            return None

        def rebucket(sig, new_b):
            return tuple((n, (new_b,) + tuple(s[1:]), dt)
                         for n, s, dt in sig)

        candidates = []
        for k in self._cache:
            if len(k) != 4 or k[0] != fp or k[2] != fetch_names \
                    or k[3] != state_names:
                continue
            cand_b = self._common_leading_dim(k[1])
            if cand_b is None or cand_b < b:
                continue
            if k[1] == rebucket(feed_sig, cand_b):
                candidates.append(cand_b)
        if policy == "pow2":
            # the pow2 bucket competes with existing entries: serving a
            # batch-5 stream must not ride a previously-compiled batch-64
            # executable forever (12.8x the compute) just because 64 was
            # seen first — one cheap 8-bucket compile amortizes at once
            candidates.append(1 << (b - 1).bit_length())
        if not candidates:
            return None
        target_b = min(candidates)
        if target_b == b:
            return None  # already a bucket boundary: compile exact
        bucket_key = (fp, rebucket(feed_sig, target_b), fetch_names,
                      state_names)
        self._bucket_map[miss_key] = (bucket_key, (b, target_b))
        return (bucket_key, self._pad_feeds(feed_vals, (b, target_b)),
                (b, target_b))

    @staticmethod
    def _pad_feeds(feed_vals, target):
        b, target_b = target
        out = {}
        for n, v in feed_vals.items():
            pad = jnp.repeat(v[-1:], target_b - b, axis=0)
            out[n] = jnp.concatenate([v, pad], axis=0)
        return out

    @classmethod
    def _unpad_fetches(cls, fetches, orig_batch, padded_batch, block=None,
                       fetch_names=()):
        """Mask-aware fetch un-padding: slice per-row fetches back to the
        real batch.  A fetch whose runtime leading dim equals the padded
        bucket is sliced unless the program says its dim 0 is NOT the
        batch (`_fetch_batch_dim_dynamic`): persistable vars (weights)
        never slice; a declared STATIC dim 0 exactly equal to the bucket
        marks a coincidence (a [64, k] temp while serving the 64-bucket)
        and passes through.  Declared dynamic (-1/None) dims, stale
        concrete dims (traced programs record the example batch), and
        undeclared temps all slice."""
        names = list(fetch_names) + [None] * (len(fetches) -
                                              len(fetch_names))
        return tuple(
            f[:orig_batch]
            if getattr(f, "ndim", 0) >= 1 and f.shape[0] == padded_batch
            and cls._fetch_batch_dim_dynamic(block, n, padded_batch)
            else f
            for f, n in zip(fetches, names))

    def memory_report(self, program=None, feed=None, scope=None,
                      batch=None, dp_shard=None):
        """Compile-time HBM accounting for one training step of
        `program` (static/memory_analysis.py): the op-IR liveness
        estimate always; XLA ground truth via
        ``jit(step).lower(...).compile().memory_analysis()`` when `feed`
        is given and the installed backend supports it.

        Returns ``{"estimate": <analyze_program dict>, "peak_bytes",
        "budget_bytes", "fits", "xla": {...} | None}``.  `batch` binds
        symbolic -1 dims for the estimate; when omitted it is inferred
        from the feed's leading dim.  The estimate needs NO device —
        fits-or-OOMs for a TPU config is answered on any host."""
        from ..core.program import default_main_program
        from .memory_analysis import analyze_program
        program = _unwrap_program(program or default_main_program())
        if batch is None and feed:
            for v in feed.values():
                shape = getattr(v, "shape", None) or np.shape(v)
                if len(shape):
                    batch = int(shape[0])
                    break
        est = analyze_program(program, batch=batch, dp_shard=dp_shard)
        report = {"estimate": est, "peak_bytes": est["peak_bytes"],
                  "budget_bytes": est["budget_bytes"],
                  "fits": est["fits"], "xla": None}
        if feed:
            scope = scope or global_scope()
            block = program.global_block()
            feed_vals = {n: self._coerce_feed(block, n, v)
                         for n, v in feed.items()}
            state_names = [n for n in _persistable_names(program)
                           if scope.get(n) is not None]
            state = {n: scope.get(n) for n in state_names}
            try:
                step = self._make_step(program, state_names, [])
                lowered = jax.jit(step, donate_argnums=(0,)).lower(
                    state, feed_vals, jnp.uint32(0))
                ma = lowered.compile().memory_analysis()
                xla = {}
                for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                    v = getattr(ma, k, None)
                    if v is not None:
                        xla[k] = int(v)
                if xla:
                    xla["peak_bytes"] = (
                        xla.get("argument_size_in_bytes", 0)
                        + xla.get("temp_size_in_bytes", 0)
                        + xla.get("output_size_in_bytes", 0)
                        - xla.get("alias_size_in_bytes", 0))
                    report["xla"] = xla
            except Exception as e:  # backend without memory_analysis()
                report["xla_error"] = repr(e)
        return report

    def cache_stats(self) -> Dict[str, int]:
        """Hot-path cache accounting for THIS executor: ``hits`` /
        ``misses`` / ``traces`` (whole-block jit retraces — the number
        that must stop growing after warmup) / ``bucket_hits`` (hits that
        needed batch padding), plus the process-wide persistent-cache
        location and entry count from core/compile_cache.py."""
        out = dict(self._stats)
        out["persistent_dir"] = _ccache.cache_dir()
        out["persistent_entries"] = _ccache.persistent_entries()
        return out

    def _make_step(self, program: Program, state_names, fetch_names):
        """(state, feed, seed) -> (fetches, state') over the whole block —
        the single traced step both the per-dispatch and scanned paths
        compile."""
        tracer = BlockTracer(program.global_block())

        def step(state, feed, seed):
            env = dict(state)
            env.update(feed)
            ctx = OpContext(seed=seed)
            tracer.run(env, ctx)
            new_state = {n: env[n] for n in state_names}
            fetches = tuple(_fetch_value(env, n, program)
                            for n in fetch_names)
            return fetches, new_state

        return step

    @staticmethod
    def _feed_signature(feed_vals):
        return tuple(sorted(
            (n, tuple(getattr(v, "shape", np.shape(v))),
             str(getattr(v, "dtype", None) or np.asarray(v).dtype))
            for n, v in feed_vals.items()))

    def _compile(self, program: Program, state_names, fetch_names):
        step = self._make_step(program, state_names, fetch_names)
        return jax.jit(step, donate_argnums=(0,))

    # -- multi-step dispatch (device-resident training loop) ----------------
    def run_steps(self, program, feed=None, fetch_list=None, scope=None,
                  return_numpy=True):
        """Run K consecutive training steps in ONE device dispatch.

        Every array in `feed` carries a leading steps axis K; the jitted
        computation `lax.scan`s the whole-block step over it, carrying the
        persistable state on device, and returns each fetch stacked to
        [K, ...].  One dispatch + one feed transfer amortize per-step host
        latency K-fold — the difference between wall throughput and device
        throughput when per-step dispatch leaves the device idle.

        TPU-first redesign of the reference's in-runtime trainer loops
        (train_from_dataset / multi-batch C++ trainer,
        paddle/fluid/framework/trainer.h:1): instead of a host loop calling
        the device once per batch, the loop itself is compiled onto the
        device.

        Stacked feeds ride the same FLAGS_feed_bucketing policy as
        run(): a ragged PER-STEP batch pads up to an already-compiled
        stacked bucket (axis 1; fetches are sliced back), and a short
        final chunk (K' < the compiled steady K) is served step-by-step
        through run() instead of retracing the whole scan — the steps
        axis is never padded, because scanned padding steps would replay
        extra optimizer updates.
        """
        with RecordEvent("Executor::RunSteps"):
            return self._dispatch_steps(program, feed, fetch_list, scope,
                                        return_numpy)

    def _dispatch_steps(self, program, feed, fetch_list, scope,
                        return_numpy):
        from ..core.program import default_main_program
        from ..distributed.compiled_program import CompiledProgram
        program = program or default_main_program()
        if isinstance(program, CompiledProgram) or (
                not isinstance(program, Program)
                and hasattr(program, "_run_steps")):
            # multi-chip scanned dispatch (incl. the elastic K-micro-step
            # window: one global step = ONE device call instead of K
            # host dispatches — distributed/elastic.py)
            results = program._run_steps(self, feed, fetch_list, scope,
                                         return_numpy)
            k = 0
            for v in (feed or {}).values():
                k = int(getattr(v, "shape", (1,))[0] or 1)
                break
            with RecordEvent("executor/observe"):
                self._observe_step(program, feed or {}, steps=max(1, k),
                                   chips=_wrapper_chips(program),
                                   stacked=True)
            with RecordEvent("executor/hooks"):
                self._maybe_checkpoint(
                    program, scope or getattr(program, "_scope", None)
                    or global_scope())
                self._chaos_step(program)
            return results
        scope = scope or global_scope()
        feed = feed or {}
        if getattr(program, "_elastic_meta", None) is not None:
            raise NotImplementedError(
                "run_steps on a RAW elastic Program is not supported: "
                "the schedule's K is resolved from the mesh at trace "
                "time, which only exists under CompiledProgram — wrap "
                "it (CompiledProgram(main).with_data_parallel(...)) and "
                "run_steps scans the K-micro-step window in one device "
                "dispatch (distributed/elastic.py)")
        fetch_names = [v.name if hasattr(v, "name") else str(v)
                       for v in (fetch_list or [])]
        block = program.global_block()
        with RecordEvent("executor/prepare"):
            feed_vals = {n: self._coerce_feed(block, n, v)
                         for n, v in feed.items()}
            if not feed_vals:
                raise ValueError("run_steps needs at least one stacked "
                                 "feed to define the number of steps")
            k = None
            for n, v in feed_vals.items():
                shape = getattr(v, "shape", ())
                if len(shape) == 0:
                    raise ValueError(
                        f"run_steps feed {n!r} is a scalar; every feed "
                        f"needs a leading steps axis (stack K per-step "
                        f"values)")
                k = shape[0] if k is None else k
                if shape[0] != k:
                    raise ValueError(
                        f"feed {n!r} leading (steps) dim {shape[0]} != "
                        f"{k}")
            state_names = [n for n in _persistable_names(program)
                           if scope.get(n) is not None]
            key = ("run_steps", program.fingerprint(),
                   self._feed_signature(feed_vals), tuple(fetch_names),
                   tuple(state_names))
            fn = self._cache.get(key)
            bucket = None  # (real per-step batch, padded per-step batch)
            if fn is None:
                bucketed = self._bucket_lookup_steps(key, feed_vals)
                if bucketed is not None:
                    key, feed_vals, bucket = bucketed
                    fn = self._cache.get(key)
        if fn is None and self.bucket_policy != "off" and \
                self._has_longer_scan(key, k):
            # short FINAL chunk (K' < a compiled steady K): padding the
            # steps axis would replay extra optimizer updates, so the
            # chunk runs step-by-step through run() — which buckets the
            # batch dim itself — instead of retracing the whole scan.
            # State threading and per-step seeds are identical to the
            # scanned path (same _seed_for_step walk over self._step).
            return self._run_steps_fallback(program, feed_vals, k,
                                            fetch_list, scope,
                                            return_numpy)
        if fn is None:
            fingerprint = str(key[1])[:16]
            self._unsettled[key] = {"mode": "run_steps",
                                    "fingerprint": fingerprint}
            with Phase("executor/trace_compile", mode="run_steps",
                       fingerprint=fingerprint):
                from .verifier import verify_first_compile
                verify_first_compile(program, fetch_list=fetch_names)
                self._record("miss")
                self._record("trace")
                from ..observability.journal import emit as _jemit
                _jemit("compile", mode="run_steps",
                       fingerprint=fingerprint)
                fn = self._compile_steps(program, state_names,
                                         fetch_names)
                self._cache[key] = fn
        else:
            self._record("hit", bucketed=bucket is not None)

        # same side contracts as run(): elastic auto-checkpoint hook,
        # run counters, profiler spans, FLAGS_check_nan_inf post-scan
        from ..incubate.checkpoint.auto_checkpoint import _auto_checkpoint
        with RecordEvent("executor/hooks"):
            _auto_checkpoint(self, program)
        from ..core.flags import flag
        from ..core.monitor import stat_add
        stat_add("executor_run_times")
        state = {n: scope.get(n) for n in state_names}
        first_launch = self._first_launch(key)
        with RecordEvent("executor/launch"), first_launch:
            seeds = jnp.asarray(
                [self._seed_for_step(program) + i for i in range(k)],
                jnp.uint32)
            fetches, new_state = fn(state, feed_vals, seeds)
        self._settle(key, first_launch)
        self._step += k
        for n, v in new_state.items():
            scope.set(n, v)
        # stacked=True explicitly: a K=1 run_steps feed still has its
        # per-step batch on axis 1, not axis 0
        with RecordEvent("executor/observe"):
            self._observe_step(program, feed_vals, steps=int(k),
                               stacked=True)
        if bucket is not None:
            fetches = self._unpad_steps_fetches(fetches, *bucket,
                                                block=block,
                                                fetch_names=fetch_names)
        if return_numpy:
            with RecordEvent("executor/fetch"):
                results = [np.asarray(f) for f in fetches]
        else:
            results = list(fetches)
        with RecordEvent("executor/hooks"):
            if flag("check_nan_inf", False):
                self._check_nan_inf(fetch_names, results, scope,
                                    program=program, steps=int(k))
            self._maybe_checkpoint(program, scope)
            self._chaos_step(program)
        return results

    def _compile_steps(self, program: Program, state_names, fetch_names):
        step = self._make_step(program, state_names, fetch_names)

        def body(state, xs):
            feed, seed = xs
            fetches, new_state = step(state, feed, seed)
            return new_state, fetches

        def multi(state, feeds, seeds):
            new_state, fetches = jax.lax.scan(body, state, (feeds, seeds))
            return fetches, new_state

        return jax.jit(multi, donate_argnums=(0,))

    # -- run_steps shape bucketing ------------------------------------------
    def _bucket_lookup_steps(self, miss_key, feed_vals):
        """run_steps analog of _bucket_lookup: on a scan-cache miss, pad
        the PER-STEP batch dim (axis 1 of every stacked feed) up to the
        smallest already-compiled stacked bucket with the SAME step
        count K.  The steps axis is never padded — extra scanned steps
        would replay extra optimizer updates.  Same duplicated-row
        caveats as run()'s bucketing (docs/perf.md)."""
        policy = self.bucket_policy
        if policy not in ("existing", "pow2") or not feed_vals:
            return None
        memo = self._bucket_map.get(miss_key)
        if memo is not None:
            bucket_key, target = memo
            return (bucket_key, self._pad_steps_feeds(feed_vals, target),
                    target)
        tag, fp, feed_sig, fetch_names, state_names = miss_key
        dims = set()
        for _, shape, _ in feed_sig:
            if len(shape) < 2:
                return None
            dims.add(int(shape[1]))
        if len(dims) != 1:
            return None
        b = dims.pop()

        def rebucket(sig, new_b):
            return tuple((n, (s[0], new_b) + tuple(s[2:]), dt)
                         for n, s, dt in sig)

        candidates = []
        for k in self._cache:
            if len(k) != 5 or k[0] != tag or k[1] != fp \
                    or k[3] != fetch_names or k[4] != state_names:
                continue
            cdims = {int(s[1]) for _, s, _ in k[2] if len(s) >= 2}
            if len(cdims) != 1:
                continue
            cand_b = cdims.pop()
            if cand_b < b:
                continue
            if k[2] == rebucket(feed_sig, cand_b):
                candidates.append(cand_b)
        if not candidates:
            return None
        target_b = min(candidates)
        if target_b == b:
            return None
        bucket_key = (tag, fp, rebucket(feed_sig, target_b), fetch_names,
                      state_names)
        self._bucket_map[miss_key] = (bucket_key, (b, target_b))
        return (bucket_key, self._pad_steps_feeds(feed_vals, (b, target_b)),
                (b, target_b))

    @staticmethod
    def _pad_steps_feeds(feed_vals, target):
        b, target_b = target
        out = {}
        for n, v in feed_vals.items():
            pad = jnp.repeat(v[:, -1:], target_b - b, axis=1)
            out[n] = jnp.concatenate([v, pad], axis=1)
        return out

    @staticmethod
    def _fetch_batch_dim_dynamic(block, name, padded_batch):
        """Shared declared-shape heuristic for fetch un-padding: does
        the program say this fetch's dim 0 is the (padded) batch?  Used
        by _unpad_fetches (run) and _unpad_steps_fetches (run_steps,
        where the per-step dim 0 is the stacked axis 1)."""
        if block is None:
            return True
        try:
            var = block.var(name)
        except (KeyError, TypeError):
            return True  # unnamed fetch / temp var without declared shape
        if getattr(var, "persistable", False):
            return False
        shape = getattr(var, "shape", None)
        if not shape or shape[0] in (-1, None):
            return True
        return shape[0] != padded_batch

    def _unpad_steps_fetches(self, fetches, orig_batch, padded_batch,
                             block=None, fetch_names=()):
        """Slice stacked fetches [K, padded_b, ...] back to the real
        per-step batch along axis 1 (the per-step dim 0)."""
        names = list(fetch_names) + [None] * (len(fetches) -
                                              len(fetch_names))
        out = []
        for f, n in zip(fetches, names):
            if getattr(f, "ndim", 0) >= 2 and f.shape[1] == padded_batch \
                    and self._fetch_batch_dim_dynamic(block, n,
                                                      padded_batch):
                f = f[:, :orig_batch]
            out.append(f)
        return tuple(out)

    def _has_longer_scan(self, miss_key, k):
        """True when a scan with the same per-step signature but MORE
        steps is already compiled — i.e. this call is the short final
        chunk of a steady run_steps loop."""
        tag, fp, feed_sig, fetch_names, state_names = miss_key

        def strip_k(sig):
            return tuple((n, tuple(s[1:]), dt) for n, s, dt in sig)

        want = strip_k(feed_sig)
        for key in self._cache:
            if len(key) != 5 or key[0] != tag or key[1] != fp \
                    or key[3] != fetch_names or key[4] != state_names:
                continue
            ks = {int(s[0]) for _, s, _ in key[2] if len(s) >= 1}
            if len(ks) == 1 and ks.pop() > k and strip_k(key[2]) == want:
                return True
        return False

    def _run_steps_fallback(self, program, feed_vals, k, fetch_list,
                            scope, return_numpy):
        """Serve a K' < K final chunk as K' single-step dispatches through
        run() (whose own cache/bucketing applies) and restack the
        fetches to the run_steps [K', ...] contract."""
        outs = []
        for i in range(k):
            outs.append(self.run(
                program, feed={n: v[i] for n, v in feed_vals.items()},
                fetch_list=fetch_list, scope=scope, return_numpy=True))
        n_fetch = len(outs[0]) if outs else 0
        stacked = [np.stack([o[j] for o in outs]) for j in range(n_fetch)]
        if return_numpy:
            return stacked
        return [jnp.asarray(s) for s in stacked]

    # -- prefetch-driven step loop ------------------------------------------
    def run_prefetched(self, program, feeds, fetch_list=None, scope=None,
                       return_numpy=True, prefetch_depth=2):
        """Generator over `feeds` (an iterable of feed dicts) with async
        double-buffered device placement: batch N+1's `device_put` rides a
        worker thread while batch N computes (reader/prefetcher.py).
        Yields each step's fetch list — iterate it to drive the loop:

            for out in exe.run_prefetched(main, batches, fetch_list=[loss]):
                ...

        Feeds arriving as `jax.Array` (already placed) pass through the
        placement stage untouched, so staged and host batches can mix."""
        from ..reader.prefetcher import Prefetcher
        pf = Prefetcher(feeds, depth=prefetch_depth)
        self._active_prefetcher = pf
        try:
            for feed in pf:
                yield self.run(program, feed=feed, fetch_list=fetch_list,
                               scope=scope, return_numpy=return_numpy)
        finally:
            self._active_prefetcher = None
            pf.close()

    # -- checkpointing (paddle_tpu/checkpoint, docs/checkpoint.md) ----------
    def enable_checkpointing(self, manager, program=None, every_n_steps=100,
                             scope=None, barrier=None):
        """Periodic async checkpoints of `program`'s persistable state.

        After every run()/run_steps() that advances ``self._step`` across
        an ``every_n_steps`` boundary, the persistables (params AND
        optimizer accumulators — in static mode both live in the scope),
        the executor step, and the RNG state are snapshotted and handed
        to `manager` for background persistence.  Also registers the
        manager's preemption state provider, so a SIGTERM final save
        captures the live state (CheckpointManager.
        install_preemption_handler).

        With ``program=None`` the hook binds to the first TRAINING
        program (one containing gradient/optimizer ops) run after
        enabling; startup and eval programs running through the same
        executor neither trigger saves nor hijack the snapshot.

        With a ``world_size > 1`` manager, `barrier` (e.g.
        ``paddle_tpu.distributed.collective.barrier``) lets the hook
        publish each staged checkpoint during the run: save → wait →
        barrier → rank-0 commit.  Without one, stages stay pending until
        the next rank-0 startup recovers them."""
        if every_n_steps < 1:
            raise ValueError("every_n_steps must be >= 1")
        self._ckpt = _CkptHook(manager=manager, program=program,
                               every=int(every_n_steps), scope=scope,
                               last=self._step)
        self._ckpt_barrier = barrier
        if getattr(manager, "world_size", 1) > 1 and barrier is None:
            import warnings
            warnings.warn(
                "multi-host CheckpointManager without barrier=: periodic "
                "checkpoints are only STAGED during the run and get "
                "committed at the next rank-0 startup; pass barrier= "
                "(e.g. paddle_tpu.distributed.collective.barrier) to "
                "publish them as training goes", RuntimeWarning,
                stacklevel=2)
        def _provider():
            # prefer the (possibly latched) registered program and the
            # scope training actually runs in, so the final preemption
            # save snapshots the same state the periodic hook does —
            # the enable-time scope may be None while every run passes
            # an explicit one
            hook = self._ckpt
            prog = (hook.program if hook else None) or program
            sc = (hook.scope or hook.run_scope) if hook else scope
            return self.checkpoint_snapshot(prog, sc)

        manager.set_state_provider(_provider)

    def disable_checkpointing(self):
        if self._ckpt is not None:
            # also detach the preemption provider: a SIGTERM after an
            # explicit disable must not commit a snapshot of whatever
            # default_main_program() happens to be
            self._ckpt.manager.set_state_provider(None)
        self._ckpt = None

    def checkpoint_snapshot(self, program=None, scope=None):
        """(step, state, extra) for CheckpointManager.save: persistable
        scope values + executor step + RNG + dataset position (when a
        run_prefetched loop is active)."""
        program = program or default_main_program()
        # CompiledProgram / ParallelExecutor wrap the real Program
        program = _unwrap_program(program)
        scope = scope or global_scope()
        state = {n: scope.get(n) for n in _persistable_names(program)
                 if scope.get(n) is not None}
        from ..core.generator import get_rng_state
        extra = {"executor_step": self._step, "rng": get_rng_state(),
                 "program_fingerprint": program.fingerprint()}
        # topology-shift sidecars: enough for restore_from_checkpoint to
        # convert layouts and re-derive schedule counters when the next
        # incarnation of this job runs at a different world size
        plan = getattr(program, "_zero_shard_plan", None)
        if plan is not None and getattr(plan, "buckets", None):
            extra["zero_shard_plan"] = plan.to_dict()
            extra["dp_degree"] = int(plan.dp_degree)
        el = getattr(program, "_elastic_meta", None)
        if el is not None:
            cnt = scope.get(el["counter"])
            extra["elastic"] = {
                "logical_dp": int(el["logical_dp"]),
                "k": int(getattr(self, "_last_elastic_k", 1)),
                "world": int(getattr(self, "_last_elastic_world", 1)),
                "counter": el["counter"], "accs": list(el["accs"]),
                # the program's own persistable micro counter is the
                # authoritative schedule position (executor _step also
                # counts startup/eval runs)
                "counter_value": int(np.asarray(cnt).reshape(-1)[0])
                if cnt is not None else self._elastic_steps}
        gm = getattr(program, "_gm_meta", None)
        if gm is not None:
            extra["gradient_merge"] = dict(gm)
        pf = self._active_prefetcher
        if pf is not None:
            extra["dataset_position"] = pf.position
        return self._step, state, extra

    def _maybe_checkpoint(self, program, scope):
        hook = self._ckpt
        if hook is None:
            return
        run_p = _unwrap_program(program)
        if hook.program is None:
            # bind to the first TRAINING program run after enabling —
            # runs of the startup or an eval program must neither latch
            # (that would silently disable checkpointing of the real
            # train loop) nor be snapshotted (their persistables lack
            # the optimizer accumulators, and restoring such a
            # checkpoint would silently reset Adam moments)
            if not (isinstance(run_p, Program) and _is_training(run_p)):
                return
            hook.program = run_p
        # compare the underlying Programs: registering the raw Program
        # but running it through CompiledProgram / ParallelExecutor (the
        # multi-chip paths) must still checkpoint
        if run_p is not _unwrap_program(hook.program):
            return
        # remember where the registered program actually runs — the
        # preemption provider snapshots this scope when none was given
        # at enable time
        hook.run_scope = scope
        if self._step - hook.last < hook.every:
            return
        step, state, extra = self.checkpoint_snapshot(
            hook.program, hook.scope or scope)
        hook.manager.save(step, state, extra=extra)
        if getattr(hook.manager, "world_size", 1) > 1 and \
                self._ckpt_barrier is not None:
            # multi-host publish: every rank staged+fsync'd, then rank 0
            # renames — never publishes a stage another rank is writing
            hook.manager.wait()
            self._ckpt_barrier()
            hook.manager.commit(step)
        hook.last = self._step

    def restore_from_checkpoint(self, manager, program=None, scope=None,
                                step=None, world=None,
                                on_mismatch="convert"):
        """Auto-resume: load the newest VALID checkpoint (corrupt ones are
        skipped by the manager), write the state back into the scope, and
        restore the executor step + RNG so per-step derived seeds replay
        identically.  Returns the restored step, or None when the
        checkpoint root is empty (fresh start).

        Topology-shifted resume (docs/elastic.md): when the checkpoint's
        program fingerprint differs from `program`'s because the
        data-parallel world changed, the state is CONVERTED instead of
        loaded as a chimera:

          * ZeRO-1 shard-count mismatch — the checkpoint's recorded
            ``ShardingPlan`` routes the bucket slots through
            ``sharding.unshard_state`` → ``sharding.reshard_state`` for
            the target program's plan (either side may also be plain);
          * elastic programs (``distributed.elastic``) fingerprint
            identically across worlds; their micro-step counter and
            executor step are re-derived for the new K = N/world
            (``world`` defaults to every local device, the same default
            mesh CompiledProgram builds);
          * ``gradient_merge`` counters are re-denominated when the
            resumed program uses a different k; a mid-window position
            rounds down to the last commit and replays the window.

        ``on_mismatch``: "convert" (default) converts when it can and
        warns otherwise; "error" raises ``CheckpointError`` on any
        unconvertible fingerprint mismatch; "warn" restores the old
        chimera behaviour with a warning only.

        The checkpoint's non-tensor sidecar survives on
        ``self.last_restored_extra`` — in particular
        ``extra["dataset_position"]`` (batches already consumed by the
        interrupted run_prefetched loop; on an elastic shift it is
        re-derived to GLOBAL batches, the unit `rebucket_feeds`
        consumes), which the caller uses to fast-forward its feed
        source::

            pos = (exe.last_restored_extra or {}).get("dataset_position", 0)
            for out in exe.run_prefetched(main, islice(feeds, pos, None)):
                ...
        """
        import warnings
        if on_mismatch not in ("convert", "error", "warn"):
            raise ValueError(
                f"on_mismatch must be 'convert', 'error' or 'warn', "
                f"got {on_mismatch!r}")
        # the manager owns the STORAGE-layer topology shift (the
        # checkpoint was written by a different rank count): forward
        # on_mismatch so 'convert' routes through the rank-merged loader
        # and 'error' names both worlds (duck-typed managers in tests
        # may not take the kwarg)
        import inspect
        load_kwargs = {"step": step}
        try:
            if "on_mismatch" in inspect.signature(
                    manager.load).parameters:
                load_kwargs["on_mismatch"] = on_mismatch
        except (TypeError, ValueError):
            pass
        ckpt = manager.load(**load_kwargs)
        if ckpt is None:
            self.last_restored_extra = None
            return None
        scope = scope or global_scope()
        extra = dict(ckpt.extra)
        state = dict(ckpt.state)
        target = _unwrap_program(program) if program is not None else None
        saved_fp = extra.get("program_fingerprint")
        if target is not None and saved_fp is not None and \
                target.fingerprint() != saved_fp:
            state = self._convert_topology_shift(
                state, extra, target, on_mismatch)
        for name, val in state.items():
            # jnp.array (copy), never jnp.asarray: a zero-copy alias of
            # host memory would be donated to XLA by the next step's
            # donate_argnums and freed/reused out from under numpy
            scope.set(name, jnp.array(val))
        self._step = int(extra.get("executor_step", ckpt.step))
        # schedule re-derivation: elastic K and gradient-merge k counters
        # are denominated in micro-steps whose meaning changes with the
        # world / the rebuilt program
        self._rederive_elastic(target, scope, extra, world)
        self._rederive_gradient_merge(target, scope, extra, warnings)
        if self._ckpt is not None:
            # enable-then-restore ordering: re-anchor the last-saved
            # marker so the next run doesn't immediately re-save the
            # state just loaded (and shift every later boundary)
            self._ckpt.last = self._step
        if "rng" in extra:
            from ..core.generator import set_rng_state
            set_rng_state(extra["rng"])
        self.last_restored_extra = dict(extra)
        from ..observability.journal import emit as _jemit
        _jemit("restore", step=int(ckpt.step),
               executor_step=int(self._step),
               global_step=extra.get("global_step"))
        return ckpt.step

    def _convert_topology_shift(self, state, extra, target, on_mismatch):
        """Fingerprint mismatch triage: convert ZeRO-1 layouts when the
        plans are recorded, otherwise warn (or raise under 'error')."""
        import warnings
        saved_plan = extra.get("zero_shard_plan")
        tgt_plan = getattr(target, "_zero_shard_plan", None)
        if tgt_plan is not None and not getattr(tgt_plan, "buckets", None):
            tgt_plan = None
        if saved_plan or tgt_plan is not None:
            from ..distributed.sharding import (reshard_state,
                                                unshard_state)
            src_dp = (saved_plan or {}).get("dp_degree", 1)
            tgt_dp = tgt_plan.dp_degree if tgt_plan is not None else 1
            try:
                converted = state
                if saved_plan:
                    converted = unshard_state(converted, saved_plan)
                if tgt_plan is not None:
                    converted = reshard_state(converted, tgt_plan)
            except (ValueError, KeyError) as e:
                # colliding names with different shapes etc. — the two
                # programs differ beyond their shard layout and the
                # relayout itself is impossible
                if on_mismatch == "error":
                    from ..checkpoint import CheckpointError
                    raise CheckpointError(
                        "fingerprint mismatch is not a pure ZeRO-1 "
                        f"shard-count change (layout conversion failed: "
                        f"{e}) — refusing the chimera restore "
                        "(on_mismatch='error')") from e
                warnings.warn(
                    "restoring a checkpoint saved from a DIFFERENT "
                    f"program (fingerprint mismatch): ZeRO-1 layout "
                    f"conversion dp={src_dp} -> dp={tgt_dp} FAILED "
                    f"({e}); loading the unconverted state — resumed "
                    "training may diverge (pass on_mismatch='error' "
                    "to refuse)", RuntimeWarning, stacklevel=3)
                return state
            state = converted
            # a PURE shard-count shift converts completely: every target
            # persistable is in the converted state.  Leftover holes mean
            # the programs differ beyond sharding — that is still a
            # chimera, and 'error' must refuse it even though a plan
            # existed
            missing = [n for n in _persistable_names(target)
                       if n not in state]
            if missing:
                if on_mismatch == "error":
                    from ..checkpoint import CheckpointError
                    raise CheckpointError(
                        "fingerprint mismatch is not a pure ZeRO-1 "
                        "shard-count change: after layout conversion "
                        f"the checkpoint still lacks {missing[:8]}"
                        f"{'...' if len(missing) > 8 else ''} — "
                        "refusing the chimera restore "
                        "(on_mismatch='error')")
                warnings.warn(
                    "restoring a checkpoint saved from a DIFFERENT "
                    "program (fingerprint mismatch): converted the "
                    f"ZeRO-1 layout dp={src_dp} -> dp={tgt_dp}, but "
                    f"{len(missing)} target vars are still absent and "
                    "keep their fresh-init values — resumed training "
                    "may diverge (pass on_mismatch='error' to refuse)",
                    RuntimeWarning, stacklevel=3)
                return state
            warnings.warn(
                "restoring a checkpoint saved from a DIFFERENT program "
                "(fingerprint mismatch): automatically converted the "
                f"ZeRO-1 optimizer-state layout dp={src_dp} -> "
                f"dp={tgt_dp} (unshard_state -> reshard_state); "
                "training resumes on the re-bucketed state",
                RuntimeWarning, stacklevel=3)
            return state
        if on_mismatch == "error":
            from ..checkpoint import CheckpointError
            raise CheckpointError(
                "checkpoint program fingerprint does not match the "
                "target program and no recorded sharding plan makes the "
                "difference convertible; pass on_mismatch='warn' to "
                "force the (diverging) chimera restore")
        warnings.warn(
            "restoring a checkpoint saved from a DIFFERENT "
            "program (fingerprint mismatch): vars absent from "
            "the checkpoint keep their fresh-init values and "
            "orphan checkpoint vars are still written — resumed "
            "training may diverge from the original run "
            "(pass on_mismatch='error' to refuse chimera loads)",
            RuntimeWarning, stacklevel=3)
        return state

    def _rederive_elastic(self, target, scope, extra, world):
        """Elastic schedule position -> the new world's denomination."""
        el_meta = getattr(target, "_elastic_meta", None) \
            if target is not None else None
        if el_meta is None or "elastic" not in extra:
            return
        import jax as _jax
        from ..distributed.elastic import rederive_schedule
        new_world = int(world) if world else len(_jax.devices())
        red = rederive_schedule(extra, new_world)
        if red is None:
            return
        self._step = red["executor_step"]
        self._elastic_steps = red["executor_step"]
        self._last_elastic_k = red["k_new"]
        self._last_elastic_world = new_world
        # CompiledProgram re-anchors for its ACTUAL mesh on first run —
        # `world` here is only the best-effort default (all devices)
        self._elastic_rebase_global = red["global_step"]
        scope.set(el_meta["counter"],
                  jnp.array(np.full((1,), red["counter_value"], np.int32)))
        if red["replayed_micro"]:
            for acc in el_meta["accs"]:
                v = scope.get(acc)
                if v is not None:
                    scope.set(acc, jnp.zeros_like(jnp.asarray(v)))
        if "dataset_position" in extra:
            # GLOBAL batches, not micro-feeds: the elastic feeding
            # pattern is rebucket_feeds over global batches, and the
            # actual mesh (hence K) may differ from the `world` default
            # used here — a K-denominated position would go stale the
            # moment CompiledProgram re-anchors for its real mesh
            extra["dataset_position"] = red["global_batches_consumed"]
        extra["global_step"] = red["global_step"]

    def _rederive_gradient_merge(self, target, scope, extra, warnings):
        """gradient_merge counter k_old -> k_new re-denomination (global
        batch preserved across a world change by scaling k)."""
        tgt_gm = getattr(target, "_gm_meta", None) \
            if target is not None else None
        saved_gm = extra.get("gradient_merge")
        if tgt_gm is None or not saved_gm:
            return
        k_old = max(1, int(saved_gm.get("k", 1)))
        k_new = max(1, int(tgt_gm.get("k", 1)))
        same_names = saved_gm.get("counter") == tgt_gm.get("counter")
        if k_old == k_new and same_names:
            return  # identical schedule: restored state is already right
        cnt = scope.get(saved_gm.get("counter")) \
            if saved_gm.get("counter") else None
        old_count = int(np.asarray(cnt).reshape(-1)[0]) \
            if cnt is not None else 0
        commits, j = divmod(old_count, k_old)
        if j:
            warnings.warn(
                f"gradient_merge resume mid-window (micro {j}/{k_old}): "
                f"rounding down to commit {commits}; the partial window "
                "replays and its accumulators are reset", RuntimeWarning,
                stacklevel=3)
        scope.set(tgt_gm["counter"],
                  jnp.array(np.full((1,), commits * k_new, np.int32)))
        for acc in tgt_gm.get("accs", []):
            v = scope.get(acc)
            if v is not None and (j or not same_names):
                scope.set(acc, jnp.zeros_like(jnp.asarray(v)))
        if "dataset_position" in extra:
            # the discarded j mid-window micro-batches must REPLAY, not
            # be skipped: re-derive the feed position to the commit
            # boundary in the NEW k's denomination (one batch per
            # micro-step), like the elastic path does
            extra["dataset_position"] = commits * k_new

    # -- helpers ------------------------------------------------------------
    def _coerce_feed(self, block, name, val):
        # x64-disabled backends (the TPU default) cannot hold 64-bit
        # values: canonicalize on the HOST side before jnp sees the array
        # — jnp.asarray(int64) emits a per-call truncation UserWarning and
        # an extra device-side cast otherwise.  Shared dtype table with
        # the prefetched path (core.dtype.canonical_np_dtype) so both
        # produce the same jit signature.
        from ..core.dtype import canonical_np_dtype
        import jax as _jax
        x64 = bool(_jax.config.jax_enable_x64)
        if not isinstance(val, _jax.Array):
            a = np.asarray(val)
            tgt = canonical_np_dtype(a.dtype, x64)
            val = a if tgt == a.dtype else a.astype(tgt)
        arr = jnp.asarray(val)
        try:
            var = block.var(name)
        except KeyError:
            return arr
        want = var.dtype
        if want is None or str(arr.dtype) == want:
            return arr
        tgt = canonical_np_dtype(np_dtype(want), x64)
        if arr.dtype != tgt:
            arr = arr.astype(tgt)
        return arr

    def _seed_for_step(self, program: Program) -> int:
        return (int(program.random_seed) * 1000003 + self._step) % (2 ** 31)
