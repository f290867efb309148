"""CompiledProgram.with_data_parallel — the ParallelExecutor analog.

Reference: /root/reference/python/paddle/fluid/compiler.py:87 CompiledProgram
→ framework/parallel_executor.cc:461 (per-device scopes, NCCL comms, SSA
graph with AllReduceOpHandle per gradient,
ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:464 CreateAllReduceOp).

TPU-native redesign: no SSA graph, no per-op scheduler threads.  The program
is rewritten once — a `c_allreduce_sum` + 1/N scale is inserted on every
parameter gradient feeding an optimizer op (same insertion point as
multi_devices_graph_pass.cc:632) — then the WHOLE block is traced under
`shard_map` over a jax.sharding.Mesh with a "dp" axis: parameters replicated,
feed batch-sharded, gradients allreduced over ICI by XLA collectives.  The
scheduler the reference needed (fast_threaded_ssa_graph_executor.cc:59) is
XLA's problem now; grad bucketing/fusion (fuse_all_reduce_op_pass) is done by
XLA's collective combiner.
"""
from __future__ import annotations

import copy
import os
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.program import Program, OpRole, unique_name
from ..ops.registry import get_op_info, OpContext
from ..profiler import Phase, RecordEvent

__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy",
           "insert_grad_allreduce"]


class ReduceStrategy:
    AllReduce = 0
    Reduce = 1


class GradientScaleStrategy:
    CoeffNumDevice = 0
    One = 1
    Customized = 2


class BuildStrategy:
    """Knob parity with details/build_strategy.h; most toggles are subsumed
    by XLA (fusion, memory optimization) and kept as accepted no-ops."""
    ReduceStrategy = ReduceStrategy
    GradientScaleStrategy = GradientScaleStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.gradient_scale_strategy = GradientScaleStrategy.CoeffNumDevice
        self.fuse_all_reduce_ops = True      # XLA collective combiner
        self.fuse_all_optimizer_ops = True   # whole-graph jit subsumes
        self.fuse_elewise_add_act_ops = True
        self.fuse_bn_act_ops = True
        self.enable_inplace = True           # buffer donation
        self.memory_optimize = True
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0
        self.nccl_comm_num = 1
        self.use_hierarchical_allreduce = False
        self.hierarchical_allreduce_inter_nranks = 0
        self.enable_sequential_execution = False
        self.remove_unnecessary_lock = True
        self.cache_runtime_context = True
        self.trainers_endpoints = []
        self.debug_graphviz_path = ""
        # TPU extension (SURVEY.md §5.7): shard the sequence dim (feed
        # dim 1) over an "sp" mesh axis of this size; ring_attention ops
        # with ring_id=1 ride it.  1 = off.
        self.sequence_parallel_degree = 1
        # TPU extension: Megatron-style tensor parallelism over a "tp"
        # mesh axis (distributed/tensor_parallel.py col/row layers;
        # params annotated dist_attr shard over it).  1 = off.
        self.tensor_parallel_degree = 1
        # fetch semantics across dp replicas: "reduce" (pmean floats /
        # pmax ints — what a training loop wants for loss metrics) or
        # "concat" (reference ParallelExecutor semantics: per-device
        # fetches concatenated along dim 0, scalars stacked to [ndev])
        self.fetch_aggregation = "reduce"


class ExecutionStrategy:
    """details/execution_strategy.h:22 — thread counts are meaningless under
    XLA; kept for API parity."""

    class ExecutorType:
        Default = 0
        Experimental = 1

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 100
        self.num_iteration_per_run = 1
        self.allow_op_delay = False
        self.use_thread_barrier = True


# op types a reduced gradient legitimately flows through between the
# reduction collective and the optimizer op's Grad slot (scaling, AMP
# casts, gradient-merge accumulate/mask plumbing, ZeRO bucket plumbing)
_REDUCE_TRANSPARENT_OPS = frozenset((
    "scale_by_world_size", "scale", "cast", "elementwise_add", "where",
    "reshape", "reshape2", "concat", "pad", "slice", "assign",
    "check_finite_and_unscale", "update_loss_scaling",
))
_REDUCE_OPS = frozenset(("c_allreduce_sum", "c_reducescatter",
                         "c_elastic_fold"))


def _grad_already_reduced(producers: Dict[str, List["OpDesc"]], name: str,
                          limit: int = 96) -> bool:
    """True when `name`'s producer chain already contains a gradient
    reduction (c_allreduce_sum / c_reducescatter), walking back only
    through the ops a reduction pass inserts — the first op outside that
    set (a real backward grad op) terminates the walk.  Makes
    insert_grad_allreduce idempotent and ZeRO-aware: applying the pass
    twice, or on a program `shard_optimizer_states` already rewrote,
    inserts nothing.

    `producers` maps each var to ALL its writers, not just the last: a
    ZeRO-2 shard accumulator is written by its `elementwise_add`
    accumulate AND its masked `where` reset — the reduction sits behind
    the accumulate, and a last-writer-only walk through the reset would
    miss it and re-reduce per-rank shards (summing unrelated slices)."""
    seen, frontier = set(), [name]
    while frontier and limit > 0:
        limit -= 1
        n = frontier.pop()
        if n in seen:
            continue
        seen.add(n)
        for op in producers.get(n, ()):
            if op.type in _REDUCE_OPS:
                return True
            if op.type not in _REDUCE_TRANSPARENT_OPS:
                continue
            frontier.extend(op.input_names())
    return False


def insert_grad_allreduce(program: Program, num_replicas_axis="dp",
                          scale=True, fp16_allreduce=None) -> Program:
    """Insert c_allreduce_sum (+ 1/N scale) on every Grad input of optimizer
    ops.  Mirrors CreateAllReduceOp insertion
    (multi_devices_graph_pass.cc:464,:632); returns a rewritten clone.

    Idempotent: a Grad input whose producer chain already contains a
    c_allreduce_sum / c_reducescatter (this pass applied twice via
    CompiledProgram + a fleet meta-optimizer, or a ZeRO-1 program from
    distributed/sharding.py) is left alone instead of double-reduced.

    fp16_allreduce (meta_optimizers/fp16_allreduce_optimizer.py analog):
    wrap the allreduce in bf16 casts, halving ICI bytes."""
    if fp16_allreduce is None:
        fp16_allreduce = getattr(program, "_fp16_allreduce", False)
    p = copy.deepcopy(program)
    block = p.global_block()
    producers: Dict[str, Any] = {}
    for op in block.ops:
        for n in op.output_names():
            producers.setdefault(n, []).append(op)
    new_ops = []
    inserted = 0
    done: Dict[str, str] = {}
    for op in block.ops:
        if op.attrs.get("zero_sharded"):
            # a ZeRO bucket update: its Grad is the reduce-scattered
            # shard (possibly behind a gradient-merge accumulator) —
            # per-rank DIFFERENT slices an allreduce would sum into
            # garbage.  The producer walk below also catches this, but
            # the stamp is the contract.
            new_ops.append(op)
            continue
        if op.attrs.get(OpRole.KEY) == OpRole.Optimize and "Grad" in op.inputs:
            gnames = op.inputs["Grad"]
            new_gnames = []
            for g in gnames:
                if g in done:
                    new_gnames.append(done[g])
                    continue
                if _grad_already_reduced(producers, g):
                    new_gnames.append(g)
                    continue
                from ..core.program import OpDesc
                src = g
                if fp16_allreduce:
                    low = unique_name(g + "@BF16")
                    block.create_var(name=low, stop_gradient=True,
                                     dtype="bfloat16")
                    new_ops.append(OpDesc(
                        "cast", {"X": [g]}, {"Out": [low]},
                        {"in_dtype": "float32", "out_dtype": "bfloat16",
                         OpRole.KEY: OpRole.Dist,
                         "op_uid": p._next_uid()}))
                    src = low
                red = unique_name(g + "@ALLREDUCE")
                block.create_var(name=red, stop_gradient=True)
                ar = OpDesc("c_allreduce_sum", {"X": [src]}, {"Out": [red]},
                            {"ring_id": 0, OpRole.KEY: OpRole.Dist,
                             "op_uid": p._next_uid()})
                new_ops.append(ar)
                inserted += 1
                if fp16_allreduce:
                    back = unique_name(g + "@FP32")
                    block.create_var(name=back, stop_gradient=True,
                                     dtype="float32")
                    new_ops.append(OpDesc(
                        "cast", {"X": [red]}, {"Out": [back]},
                        {"in_dtype": "bfloat16", "out_dtype": "float32",
                         OpRole.KEY: OpRole.Dist,
                         "op_uid": p._next_uid()}))
                    red = back
                if scale:
                    scaled = unique_name(g + "@SCALED")
                    block.create_var(name=scaled, stop_gradient=True)
                    sc = OpDesc("scale_by_world_size", {"X": [red]},
                                {"Out": [scaled]},
                                {"ring_id": 0, OpRole.KEY: OpRole.Dist,
                                 "op_uid": p._next_uid()})
                    new_ops.append(sc)
                    red = scaled
                done[g] = red
                new_gnames.append(red)
            op.inputs["Grad"] = new_gnames
        new_ops.append(op)
    block.ops = new_ops
    if inserted:
        # record only EFFECTIVE applications: the idempotent re-apply
        # path (with_data_parallel over an already-reduced program)
        # inserts nothing and must not misreport history
        from ..core.pass_framework import record_applied
        record_applied(p, "grad_allreduce", scale=bool(scale),
                       fp16=bool(fp16_allreduce), reductions=inserted)
    return p


class CompiledProgram:
    """compiler.py:87 parity.  `places` defaults to all local devices."""

    def __init__(self, program_or_graph, build_strategy: BuildStrategy = None):
        self._program: Program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._loss_name = None
        self._places = None
        self._share_vars_from = None
        self._compiled = None  # (key -> jitted)
        self._cache: Dict[Any, Any] = {}
        self._mesh: Optional[Mesh] = None
        self._rewritten: Optional[Program] = None
        # device dispatches issued (one per _run, one per _run_steps
        # scan — the number the elastic run_steps K→1 claim is about)
        self._dispatches = 0

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._share_vars_from = share_vars_from
        self._places = places
        return self

    # -- execution (called from Executor.run) -------------------------------
    def _devices(self):
        if self._places is not None:
            devs = []
            for pl in self._places:
                if hasattr(pl, "jax_device"):
                    devs.append(pl.jax_device())
                else:
                    devs.append(pl)
            return devs
        return list(jax.devices())

    def _get_mesh(self) -> Mesh:
        if self._mesh is None:
            # axis names come from the shared canonicalizer
            # (core/mesh_axes.py) so the runtime mesh and the layout
            # analyzer can never disagree on the tensor axis's name
            from ..core.mesh_axes import (DP_AXIS, SP_AXIS,
                                          MP_AXIS_CANONICAL, runtime_axis)
            devs = np.array(self._devices())
            sp = max(1, int(getattr(self._build_strategy,
                                    "sequence_parallel_degree", 1)))
            tp = max(1, int(getattr(self._build_strategy,
                                    "tensor_parallel_degree", 1)))
            if sp > 1 and tp > 1:
                raise NotImplementedError(
                    "sequence_parallel_degree and tensor_parallel_degree "
                    "cannot both exceed 1 in one CompiledProgram")
            if sp > 1:
                dp = len(devs) // sp
                self._mesh = Mesh(devs[: dp * sp].reshape(dp, sp),
                                  (DP_AXIS, SP_AXIS))
            elif tp > 1:
                dp = len(devs) // tp
                self._mesh = Mesh(
                    devs[: dp * tp].reshape(dp, tp),
                    (DP_AXIS, runtime_axis(MP_AXIS_CANONICAL)))
            else:
                self._mesh = Mesh(devs, (DP_AXIS,))
        return self._mesh

    def _get_program(self) -> Program:
        if self._rewritten is None:
            n = len(self._devices())
            has_zero = any(
                v.attrs.get("dp_shard")
                for b in self._program.blocks for v in b.vars.values())
            has_elastic = getattr(self._program, "_elastic_meta",
                                  None) is not None
            # dp×tp composes: ring 0 binds to the dp sub-axis only (the
            # dist_info registry in _traced_step), so the ZeRO bucket
            # reduce-scatter, the grad allreduce, and the elastic
            # ordered fold all reduce over dp while the tp leg stays
            # intact — tp-partial activations are already completed by
            # the builders' mp_allreduce_sum, tp-sharded weight grads
            # are per-shard values that must NOT cross the tp axis, and
            # dp_shard slot buckets place P("dp") on the 2-D mesh
            # (replicated over tp).  dp×sp still refuses: there
            # gradients are partial over BOTH axes and a dp-only
            # reduction silently drops the sp contributions.
            if has_elastic and int(getattr(
                    self._build_strategy,
                    "sequence_parallel_degree", 1)) > 1:
                raise NotImplementedError(
                    "elastic programs (distributed/elastic.elasticize) "
                    "compose with dp or dp×tp meshes only; the ordered "
                    "fold reduces ring 0's dp axis, but under dp×sp "
                    "gradients are partial over both axes "
                    "(sequence_parallel_degree must be 1)")
            if has_zero and int(getattr(
                    self._build_strategy,
                    "sequence_parallel_degree", 1)) > 1:
                raise NotImplementedError(
                    "ZeRO-1 sharded programs (shard_optimizer_states) "
                    "compose with dp or dp×tp meshes only; the bucket "
                    "reduce-scatter rides ring 0's dp axis, but under "
                    "dp×sp gradients are partial over both axes "
                    "(sequence_parallel_degree must be 1)")
            if self._is_data_parallel:
                scale = (self._build_strategy.gradient_scale_strategy ==
                         GradientScaleStrategy.CoeffNumDevice and n > 1)
                rewritten = insert_grad_allreduce(self._program, scale=scale)
            else:
                rewritten = self._program
            # BuildStrategy-driven graph passes (build_strategy.cc:58-237
            # pass-pipeline assembly analog; core/pass_framework.py)
            from ..core.pass_framework import apply_passes, PassContext
            names = []
            if self._build_strategy.sync_batch_norm and \
                    self._is_data_parallel and n > 1:
                names.append("sync_batch_norm_pass")
            if getattr(self._build_strategy, "debug_graphviz_path", ""):
                names.append("graph_viz_pass")
            if names:
                ctx = PassContext(graph_viz_path=self._build_strategy
                                  .debug_graphviz_path or "program.dot")
                rewritten = apply_passes(rewritten, names, ctx)
            self._rewritten = rewritten
        return self._rewritten

    def _anchor_elastic(self, executor, scope, elastic, n_dev) -> int:
        """Resolve K for THIS mesh and re-anchor a topology-shifted
        restore's counters against it; returns micro_k.  `n_dev` is the
        mesh's DP degree — under a dp×tp mesh the elastic schedule folds
        over dp sub-ranks only (the tp leg is model parallelism, not
        extra data-parallel capacity)."""
        n_logical = int(elastic["logical_dp"])
        if n_logical % n_dev != 0:
            raise ValueError(
                f"elastic logical_dp={n_logical} is not divisible by "
                f"the mesh dp degree {n_dev}")
        micro_k = n_logical // n_dev
        # topology-shifted resume: restore_from_checkpoint left the
        # schedule position in GLOBAL steps (it cannot know the new
        # mesh); re-anchor the executor's micro-step counter for THIS
        # world before deriving seeds from it
        rebase = getattr(executor, "_elastic_rebase_global", None)
        if rebase is not None:
            from ..observability.journal import emit as _jemit
            _jemit("reanchor", world=int(n_dev), k=int(micro_k),
                   global_step=int(rebase))
            executor._step = int(rebase) * micro_k
            executor._elastic_steps = int(rebase) * micro_k
            # the restore re-derived the persistable micro counter
            # for its best-guess default world; THIS mesh is the
            # authority — re-anchor it too, or the commit mask and
            # per-rank RNG phase run at the wrong K (e.g. restore on
            # an 8-device host, then places=4: counter g vs step
            # g*2 would commit after ONE half-folded micro-step)
            scope.set(elastic["counter"],
                      jnp.array(np.full((1,), int(rebase) * micro_k,
                                        np.int32)))
            executor._elastic_rebase_global = None
        executor._last_elastic_world = n_dev
        executor._last_elastic_k = micro_k
        return micro_k

    def _run(self, executor, feed, fetch_list, scope, return_numpy):
        from ..static.executor import (global_scope, BlockTracer,
                                       _persistable_names)
        scope = scope or global_scope()
        feed = feed or {}
        fetch_names = [f.name if hasattr(f, "name") else str(f)
                       for f in (fetch_list or [])]
        program = self._get_program()
        mesh = self._get_mesh()
        n_dev = len(mesh.devices.flat)
        block = program.global_block()

        elastic = getattr(program, "_elastic_meta", None)
        micro_k = 1
        if elastic is not None:
            micro_k = self._anchor_elastic(executor, scope, elastic,
                                           int(mesh.shape["dp"]))

        # pre-placed feeds (reader.Prefetcher via place_feed) pass through;
        # host arrays go straight to the shards the step reads them from
        with RecordEvent("mesh/place_feed"):
            feed_vals = self._place_host_feeds(
                feed, self._feed_specs(program, mesh, sorted(feed)), mesh)
        from ..core import compile_cache as _ccache
        with RecordEvent("executor/prepare"):
            state_names = [n for n in _persistable_names(program)
                           if scope.get(n) is not None]
            feed_sig = tuple(sorted((n, tuple(v.shape), str(v.dtype))
                                    for n, v in feed_vals.items()))
            key = (program.fingerprint(), feed_sig, tuple(fetch_names),
                   tuple(state_names), n_dev,
                   getattr(self._build_strategy, "fetch_aggregation",
                           "reduce"))
            fn = self._cache.get(key)
        if fn is None:
            fingerprint = str(key[0])[:16]
            executor._unsettled[key] = {"mode": "compiled",
                                        "fingerprint": fingerprint}
            with Phase("executor/trace_compile", mode="compiled",
                       fingerprint=fingerprint):
                # env-gated IR verification rides the (already slow)
                # first compile of each program (PADDLE_TPU_VERIFY,
                # verifier.py)
                from ..static.verifier import verify_first_compile
                verify_first_compile(program, fetch_list=fetch_names)
                _ccache.record_miss()
                _ccache.record_trace()
                from ..observability.journal import emit as _jemit
                _jemit("compile", mode="compiled", world=int(n_dev),
                       fingerprint=fingerprint)
                fn = self._compile(program, state_names,
                                   sorted(feed_vals), fetch_names, mesh)
                self._cache[key] = fn
        else:
            _ccache.record_hit()

        from ..testing import chaos as _chaos
        if _chaos.enabled():
            # same step numbering as the kill hook: the n-th TRAIN step
            # (startup/eval dispatches neither count nor fault)
            if getattr(program, "_chaos_is_training", None) is None:
                from ..static.executor import _is_training
                program._chaos_is_training = _is_training(program)
            if program._chaos_is_training:
                _chaos.collective_hook(executor._train_runs + 1)
        state = {n: scope.get(n) for n in state_names}
        if elastic is not None:
            # one RNG stream per GLOBAL step: all K micro-steps of a
            # window derive from the same base seed, decorrelated per
            # LOGICAL rank inside the traced step — so dropout masks and
            # shuffles replay identically on any mesh size.  Counted by
            # _elastic_steps, which (unlike _step) startup/eval runs
            # never pollute.
            seed = (int(program.random_seed) * 1000003 +
                    executor._elastic_steps // micro_k) % (2 ** 31)
        else:
            seed = executor._seed_for_step(program)
        first_launch = executor._first_launch(key)
        with RecordEvent("executor/launch"), first_launch:
            fetches, new_state = fn(state, feed_vals, jnp.uint32(seed))
        executor._settle(key, first_launch)
        self._dispatches += 1
        executor._step += 1
        if elastic is not None:
            executor._elastic_steps += 1
        for n, v in new_state.items():
            scope.set(n, v)
        if return_numpy:
            with RecordEvent("executor/fetch"):
                return [np.asarray(f) for f in fetches]
        return list(fetches)

    def place_feed(self, feed: Dict[str, Any]) -> Dict[str, Any]:
        """Async-friendly sharded feed placement: ship a host batch onto
        the mesh with the same batch-dim layout `_run`'s shard_map expects
        (dim 0 split over "dp" when it divides evenly, else replicated).
        Designed as a `reader.Prefetcher` place_fn so the host→ICI
        transfer of batch N+1 overlaps the sharded compute of batch N:

            pf = Prefetcher(batches, place_fn=compiled.place_feed)
            for feed in pf: exe.run(compiled, feed=feed, ...)
        """
        with RecordEvent("mesh/place_feed"):
            mesh = self._get_mesh()
            dp = mesh.shape["dp"]
            specs = {n: P("dp") if np.ndim(v) >= 1
                     and np.shape(v)[0] % dp == 0 else P()
                     for n, v in feed.items()}
            return self._place_host_feeds(feed, specs, mesh)

    @staticmethod
    def _feed_specs(program, mesh, feed_names):
        """Per-dispatch feed in_specs — what `_compile` traces against
        and what `_run` places host feeds by."""
        if "sp" in mesh.axis_names:
            # batch over dp, sequence (dim 1) over sp; rank-1 feeds
            # (e.g. flat labels) shard batch only
            block = program.global_block()
            sp_deg = mesh.shape["sp"]
            feed_specs = {}
            for n in feed_names:
                try:
                    shape = tuple(block.var(n).shape or ())
                except KeyError:
                    shape = ()
                # sequence dim (dim 1) rides sp only when it divides evenly
                # ([-1, 1] label feeds and ragged dims shard batch only)
                if len(shape) >= 2 and shape[1] is not None and \
                        shape[1] > 1 and shape[1] % sp_deg == 0:
                    feed_specs[n] = P("dp", "sp")
                else:
                    feed_specs[n] = P("dp")
            return feed_specs
        # the partition-spec engine: P("dp") batch split for training
        # feeds (the historical default), dist_attr head-dim tp shards
        # and replicated_feed P() for the tp-decode serving programs
        from .partition_spec import feed_partition_specs
        return feed_partition_specs(program, mesh, feed_names)

    @staticmethod
    def _steps_feed_specs(program, mesh, feed_shapes):
        """Stacked ([K, per-step...]) feed in_specs for the scanned
        paths, from the feeds' runtime shapes."""
        dp = mesh.shape["dp"]
        has_sp = "sp" in mesh.axis_names
        sp_deg = mesh.shape["sp"] if has_sp else 1
        block = program.global_block()
        feed_specs = {}
        for n, shape in feed_shapes.items():
            # steps axis never shards; the per-step batch (axis 1)
            # shards over dp like the looped path's P("dp").  A
            # non-divisible batch must FAIL here like it does there —
            # silently replicating it would run every rank over the
            # full batch with a different summation order, breaking
            # the bitwise-to-looped contract
            if len(shape) >= 2:
                if shape[1] % dp != 0:
                    raise ValueError(
                        f"run_steps feed {n!r} per-step batch "
                        f"{shape[1]} does not divide the dp world "
                        f"{dp} (stacked feeds shard axis 1 over dp, "
                        "like run() shards axis 0)")
                if has_sp:
                    # mirror _feed_specs' sp heuristic one axis right:
                    # the declared per-step dim 1 (sequence) is the
                    # stacked axis 2
                    try:
                        gshape = tuple(block.var(n).shape or ())
                    except KeyError:
                        gshape = ()
                    if len(gshape) >= 2 and gshape[1] is not None and \
                            gshape[1] > 1 and gshape[1] % sp_deg == 0 \
                            and len(shape) >= 3 and \
                            shape[2] % sp_deg == 0:
                        feed_specs[n] = P(None, "dp", "sp")
                    else:
                        feed_specs[n] = P(None, "dp")
                else:
                    feed_specs[n] = P(None, "dp")
            else:
                feed_specs[n] = P(None)  # [K] per-step scalars
        return feed_specs

    @staticmethod
    def _place_host_feeds(feed, specs, mesh):
        """Host arrays -> mesh arrays laid out as the compiled step's
        in_specs read them: one host->device copy per shard.  A bare
        `jnp.asarray` (what `specs=None` asks for) lands each batch whole
        on device 0 and leaves the jit to scatter it from there.
        `jax.Array`s pass through."""
        from jax.sharding import NamedSharding
        from ..reader.prefetcher import _canonical_array, _x64_enabled
        x64 = _x64_enabled()
        out = {}
        for n, v in feed.items():
            if not isinstance(v, jax.Array):
                v = _canonical_array(v, x64)
                v = jnp.asarray(v) if specs is None else jax.device_put(
                    v, NamedSharding(mesh, specs[n]))
            out[n] = v
        return out

    def _run_steps(self, executor, feed, fetch_list, scope, return_numpy):
        """K steps in ONE device dispatch (Executor.run_steps contract)
        over the sharded mesh: the traced step `lax.scan`s over the
        stacked feeds' leading axis with the persistable state carried
        on device.

        For an elastic program this is the dispatch-collapse the
        ROADMAP names: a global step is K = logical_dp/world
        micro-steps, and driving them through run() pays K host
        dispatch round-trips per global step; feeding the K re-bucketed
        micro-feeds stacked ([K, M·b, ...]) runs the whole commit
        window as ONE device call, bitwise-equal to the looped form
        (same traced step, same per-window seed derivation — the
        per-micro-step RNG phase comes from the persistable counter
        carried through the scan).

        For a gradient-merge (optionally ×ZeRO) program whose K is a
        whole number of commit windows and whose counter sits on a
        window boundary, the scan runs HOISTED (scan_window.py): the
        commit tail — optimizer update, publish allgather, merged-grad
        allreduce — executes once per gm-K window instead of once per
        micro-step, cutting the publish wire to 1/K.  The arithmetic is
        unchanged (the looped commit is masked off on the same steps);
        the last bit is the compiler's: on XLA:CPU losses stay bit-equal
        to the looped path and a float32 optimizer moment moves by up to
        one ulp per window (tools/scan_smoke.py).
        ``PADDLE_TPU_SCAN_HOIST=0`` forces the unhoisted scan.

        Stacked feeds ride the executor's FLAGS_feed_bucketing policy:
        a ragged PER-STEP batch pads up to an already-compiled stacked
        bucket (axis 1) under ``fetch_aggregation="reduce"`` — same
        duplicated-row caveats as run()'s bucketing (docs/perf.md).
        The steps axis is never padded."""
        from ..static.executor import global_scope, _persistable_names
        scope = scope or global_scope()
        feed = feed or {}
        if not feed:
            raise ValueError(
                "run_steps needs at least one stacked feed to define "
                "the number of steps")
        fetch_names = [f.name if hasattr(f, "name") else str(f)
                       for f in (fetch_list or [])]
        program = self._get_program()
        mesh = self._get_mesh()
        if set(mesh.axis_names) - {"dp", "tp", "sp"}:
            raise NotImplementedError(
                "run_steps through CompiledProgram supports dp, dp×tp "
                "and dp×sp meshes only")
        n_dev = len(mesh.devices.flat)
        elastic = getattr(program, "_elastic_meta", None)
        micro_k = 1
        if elastic is not None:
            micro_k = self._anchor_elastic(executor, scope, elastic,
                                           int(mesh.shape["dp"]))
        k = None
        for n, v in feed.items():
            shape = tuple(np.shape(v))
            if len(shape) == 0:
                raise ValueError(
                    f"run_steps feed {n!r} is a scalar; every feed "
                    "needs a leading steps axis")
            k = shape[0] if k is None else k
            if shape[0] != k:
                raise ValueError(
                    f"feed {n!r} leading (steps) dim {shape[0]} != {k}")
        k = int(k)
        # a ragged per-step batch stays unplaced: the bucket lookup
        # below pads it (or _compile_steps refuses it)
        shapes = {n: tuple(np.shape(v)) for n, v in feed.items()}
        dp = mesh.shape["dp"]
        even = all(len(s) < 2 or s[1] % dp == 0 for s in shapes.values())
        with RecordEvent("mesh/place_feed"):
            feed_vals = self._place_host_feeds(
                feed, self._steps_feed_specs(program, mesh, shapes)
                if even else None, mesh)
        state_names = [n for n in _persistable_names(program)
                       if scope.get(n) is not None]

        # commit-tail hoist eligibility: a splittable gm window, K a
        # whole number of windows, and the persistable counter on a
        # window boundary (a mid-window start must replay the masked
        # looped semantics — the plain scan does exactly that)
        split = None
        if elastic is None and \
                os.environ.get("PADDLE_TPU_SCAN_HOIST", "1").lower() \
                not in ("0", "false", "off"):
            split = self._window_split(program, tuple(fetch_names))
        hoist = False
        if split is not None and k % split.k == 0:
            cval = scope.get(split.counter)
            if cval is not None:
                cnt = int(np.asarray(cval).reshape(-1)[0])
                hoist = cnt % split.k == 0
        agg = getattr(self._build_strategy, "fetch_aggregation", "reduce")
        feed_sig = tuple(sorted((n, tuple(v.shape), str(v.dtype))
                                for n, v in feed_vals.items()))
        key = ("steps", bool(hoist), program.fingerprint(), feed_sig,
               tuple(fetch_names), tuple(state_names), n_dev, agg)
        from ..core import compile_cache as _ccache
        fn = self._cache.get(key)
        bucket = None  # (real per-step batch, padded per-step batch)
        if fn is None and agg == "reduce":
            bucketed = self._bucket_lookup_steps(executor, key, feed_vals)
            if bucketed is not None:
                key, feed_vals, bucket = bucketed
                fn = self._cache.get(key)
        if fn is None:
            mode = "compiled_steps_hoisted" if hoist else "compiled_steps"
            fingerprint = str(key[2])[:16]
            executor._unsettled[key] = {"mode": mode,
                                        "fingerprint": fingerprint}
            with Phase("executor/trace_compile", mode=mode,
                       fingerprint=fingerprint):
                from ..static.verifier import verify_first_compile
                verify_first_compile(program, fetch_list=fetch_names)
                _ccache.record_miss()
                _ccache.record_trace()
                from ..observability.journal import emit as _jemit
                _jemit("compile", mode=mode, world=int(n_dev),
                       fingerprint=fingerprint)
                fn = self._compile_steps(program, state_names, feed_vals,
                                         fetch_names, mesh,
                                         split=split if hoist else None)
                self._cache[key] = fn
        else:
            _ccache.record_hit()
        from ..testing import chaos as _chaos
        if _chaos.enabled():
            if getattr(program, "_chaos_is_training", None) is None:
                from ..static.executor import _is_training
                program._chaos_is_training = _is_training(program)
            if program._chaos_is_training:
                _chaos.collective_hook(executor._train_runs + 1)
        state = {n: scope.get(n) for n in state_names}
        if elastic is not None:
            # one RNG stream per GLOBAL step, same derivation as K
            # looped _run calls would walk (scanned micro-step i of
            # this window belongs to global step
            # (elastic_steps + i) // K)
            base = int(program.random_seed) * 1000003
            seeds = jnp.asarray(
                [(base + (executor._elastic_steps + i) // micro_k)
                 % (2 ** 31) for i in range(k)], jnp.uint32)
        else:
            # (x % m + i) % m == (x + i) % m: re-applying the modulus
            # keeps micro-step i's seed EXACTLY what the i-th looped
            # _run call would derive, across the 2**31 wrap included
            seeds = jnp.asarray(
                [(executor._seed_for_step(program) + i) % (2 ** 31)
                 for i in range(k)], jnp.uint32)
        first_launch = executor._first_launch(key)
        with RecordEvent("executor/launch"), first_launch:
            fetches, new_state = fn(state, feed_vals, seeds)
        executor._settle(key, first_launch)
        self._dispatches += 1
        executor._step += k
        if elastic is not None:
            executor._elastic_steps += k
        for n, v in new_state.items():
            scope.set(n, v)
        if bucket is not None:
            fetches = executor._unpad_steps_fetches(
                fetches, bucket[0], bucket[1],
                block=program.global_block(), fetch_names=fetch_names)
        if return_numpy:
            with RecordEvent("executor/fetch"):
                return [np.asarray(f) for f in fetches]
        return list(fetches)

    def _compile_steps(self, program, state_names, feed_vals,
                       fetch_names, mesh, split=None):
        """jit(shard_map(scan(step))): the scanned sibling of _compile
        (dp / dp×tp / dp×sp meshes; feeds carry [K, per-step...] with
        the per-step batch on axis 1).

        With `split` (a scan_window.WindowSplit) the scan runs the
        HOISTED window: an outer scan over K/gm_k windows, each window
        an inner scan of gm_k commit-free body steps followed by ONE
        commit-tail execution — the publish allgather and merged-grad
        allreduce run once per window instead of once per micro-step."""
        from .partition_spec import state_partition_specs

        if split is not None:
            step = self._traced_step(split.body, state_names,
                                     fetch_names, mesh)
            # the tail is a pure function of persistable state (the
            # splitter's soundness contract): no feed, no fetches — it
            # recomputes the mask from the carried counter and commits
            tail_step = self._traced_step(split.tail, state_names, [],
                                          mesh)
            gm_k = int(split.k)
        else:
            step = self._traced_step(program, state_names, fetch_names,
                                     mesh)

        def body(state, xs):
            feed, seed = xs
            fetches, new_state = step(state, feed, seed)
            return new_state, fetches

        if split is not None:
            def window(state, xs):
                feeds_w, seeds_w = xs
                state, fetches = jax.lax.scan(body, state,
                                              (feeds_w, seeds_w))
                # tail has no RNG ops (splitter contract: persistable
                # reads only) — the seed argument is inert
                _, state = tail_step(state, {}, seeds_w[-1])
                return state, fetches

            def multi(state, feeds, seeds):
                k = seeds.shape[0]
                m = k // gm_k
                feeds_w = {n: v.reshape((m, gm_k) + v.shape[1:])
                           for n, v in feeds.items()}
                seeds_w = seeds.reshape((m, gm_k))
                new_state, fetches = jax.lax.scan(window, state,
                                                  (feeds_w, seeds_w))
                fetches = tuple(f.reshape((k,) + f.shape[2:])
                                for f in fetches)
                return fetches, new_state
        else:
            def multi(state, feeds, seeds):
                new_state, fetches = jax.lax.scan(body, state,
                                                  (feeds, seeds))
                return fetches, new_state

        state_specs = state_partition_specs(program, mesh, state_names)
        feed_specs = self._steps_feed_specs(
            program, mesh, {n: tuple(v.shape) for n, v in feed_vals.items()})
        fetch_specs = tuple(P() for _ in fetch_names)
        sharded = jax.shard_map(
            multi, mesh=mesh, in_specs=(state_specs, feed_specs, P()),
            out_specs=(fetch_specs, state_specs), check_vma=False)
        return jax.jit(sharded, donate_argnums=(0,))

    def _window_split(self, program, fetch_names):
        """Cached scan_window.split_commit_tail — the split walks (and
        clones) the whole program, so _run_steps memoizes it per
        (fingerprint, fetches)."""
        key = (program.fingerprint(), tuple(fetch_names))
        cached = getattr(self, "_scan_split_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from .scan_window import split_commit_tail
        split = split_commit_tail(program, fetch_names)
        self._scan_split_cache = (key, split)
        return split

    def _bucket_lookup_steps(self, executor, miss_key, feed_vals):
        """CompiledProgram analog of Executor._bucket_lookup_steps: on
        a scanned-cache miss under the executor's FLAGS_feed_bucketing
        policy, pad the PER-STEP batch (axis 1 of every stacked feed)
        up to the smallest already-compiled stacked bucket with the
        same step count — the steps axis is never padded.  Only under
        ``fetch_aggregation="reduce"`` (concat fetches interleave
        per-shard rows, which un-padding cannot unpick); padded
        duplicate rows carry the same caveats as run()'s bucketing."""
        policy = getattr(executor, "bucket_policy", "off")
        if policy not in ("existing", "pow2") or not feed_vals:
            return None
        memo = getattr(self, "_steps_bucket_map", None)
        if memo is None:
            memo = self._steps_bucket_map = {}
        hit = memo.get(miss_key)
        if hit is not None:
            bucket_key, target = hit
            return (bucket_key,
                    executor._pad_steps_feeds(feed_vals, target), target)
        tag, hoist, fp, feed_sig, rest = (miss_key[0], miss_key[1],
                                          miss_key[2], miss_key[3],
                                          miss_key[4:])
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
            if len(k) != len(miss_key) or k[0] != tag or k[1] != hoist \
                    or k[2] != fp or k[4:] != rest:
                continue
            cdims = {int(s[1]) for _, s, _ in k[3] if len(s) >= 2}
            if len(cdims) != 1:
                continue
            cand_b = cdims.pop()
            if cand_b < b:
                continue
            if k[3] == rebucket(feed_sig, cand_b):
                candidates.append(cand_b)
        if not candidates:
            return None
        target_b = min(candidates)
        if target_b == b:
            return None
        bucket_key = (tag, hoist, fp, rebucket(feed_sig, target_b)) + rest
        memo[miss_key] = (bucket_key, (b, target_b))
        return (bucket_key,
                executor._pad_steps_feeds(feed_vals, (b, target_b)),
                (b, target_b))

    def _traced_step(self, program, state_names, fetch_names, mesh):
        """The single traced (state, feed, seed) -> (fetches, state')
        step both the per-dispatch (`_compile`) and scanned
        (`_compile_steps`) paths wrap in shard_map."""
        from ..static.executor import BlockTracer, _fetch_value
        block = program.global_block()
        tracer = BlockTracer(block)
        axes = tuple(mesh.axis_names)
        has_sp = "sp" in axes
        has_tp = "tp" in axes
        fetch_aggregation = getattr(self._build_strategy,
                                    "fetch_aggregation", "reduce")
        if fetch_aggregation not in ("reduce", "concat"):
            raise ValueError(
                f"BuildStrategy.fetch_aggregation must be 'reduce' or "
                f"'concat', got {fetch_aggregation!r}")

        elastic = getattr(program, "_elastic_meta", None)
        n_mesh_dp = mesh.shape["dp"]
        micro_k = 1
        if elastic is not None:
            micro_k = int(elastic["logical_dp"]) // n_mesh_dp

        def step(state, feed, seed):
            # decorrelate RNG across replicas (the reference gives each
            # device worker a distinct seed).  NOT across tp: tp shards
            # see the same batch and must draw identical dropout masks.
            if elastic is not None:
                # elastic: decorrelate by LOGICAL rank jM+m (micro-step j
                # from the persistable counter, pre-increment), so every
                # topology draws the same per-rank streams
                cnt = jnp.reshape(state[elastic["counter"]], (-1,))[0]
                micro = jnp.mod(cnt.astype(jnp.uint32),
                                jnp.uint32(micro_k))
                local_seed = seed + micro * jnp.uint32(n_mesh_dp) + \
                    jnp.uint32(jax.lax.axis_index("dp"))
            else:
                local_seed = seed + jnp.uint32(jax.lax.axis_index("dp"))
            if has_sp:
                local_seed = local_seed * jnp.uint32(7919) + \
                    jnp.uint32(jax.lax.axis_index("sp"))
            # ring 0 = dp world (grad allreduce); ring 1 = sequence axis
            # SP_RING_ID is the reserved sequence ring (not bound without
            # an sp axis → ring_attention degrades to plain attention).
            # Under dp×sp, gradients are partial over BOTH axes (batch and
            # sequence shards), so ring 0 reduces over the whole mesh;
            # under dp×tp, grads reduce over dp ONLY (tp shards either
            # hold disjoint weight shards or identical replicated grads)
            # and TP_RING_ID binds the Megatron collectives to "tp".
            from ..ops.attention import SP_RING_ID
            from .tensor_parallel import TP_RING_ID
            # TP_RING_ID binds to None when no tp axis exists: the weights
            # are then unsharded, every shard computes the full product,
            # and the Megatron collectives must degrade to identity (like
            # SP_RING_ID) — falling through to the dp axis would psum
            # complete outputs across batch shards
            if has_sp:
                dist_info = {0: ("dp", "sp"), SP_RING_ID: "sp",
                             TP_RING_ID: None, "default": "dp"}
            elif has_tp:
                dist_info = {0: "dp", SP_RING_ID: None,
                             TP_RING_ID: "tp", "default": "dp"}
            else:
                dist_info = {0: "dp", SP_RING_ID: None, TP_RING_ID: None}
            ctx = OpContext(seed=local_seed, mesh_axes=axes,
                            dist_info=dist_info)
            env = dict(state)
            env.update(feed)
            tracer.run(env, ctx)
            new_state = {n: env[n] for n in state_names}
            fetches = []
            for n in fetch_names:
                v = _fetch_value(env, n, program)
                if elastic is not None and (
                        n == elastic.get("loss_avg")
                        or n in elastic.get("accs", ())):
                    # elastic fold outputs are already replicated AND
                    # globally averaged; pmean-ing n identical replicas
                    # computes nL/n, whose rounding depends on the world
                    # size — exactly the variance elastic mode removes
                    fetches.append(v)
                    continue
                if fetch_aggregation == "concat":
                    # reference ParallelExecutor semantics: per-device rows
                    # concatenated along dim 0 (scalars stack to [ndev]).
                    if has_sp:
                        # mirror the feed-spec heuristic: only dim-1
                        # sequence shards reassemble along dim 1; anything
                        # replicated/reduced over sp is averaged
                        try:
                            gshape = tuple(block.var(n).shape or ())
                        except KeyError:
                            gshape = ()
                        sp_sharded = (len(gshape) >= 2
                                      and gshape[1] is not None
                                      and gshape[1] > 1
                                      and gshape[1] % mesh.shape["sp"] == 0)
                        if v.ndim >= 2 and sp_sharded:
                            v = jax.lax.all_gather(v, "sp", axis=1,
                                                   tiled=True)
                        elif jnp.issubdtype(v.dtype, jnp.inexact):
                            # per-example reductions (loss) are replicated
                            # partial means over sp — average them
                            v = jax.lax.pmean(v, "sp")
                        else:
                            v = jax.lax.pmax(v, "sp")
                    if v.ndim == 0:
                        v = jax.lax.all_gather(v, "dp")
                    else:
                        v = jax.lax.all_gather(v, "dp", tiled=True)
                elif jnp.issubdtype(v.dtype, jnp.inexact):
                    # "reduce": average floats (what a training loop wants
                    # for loss metrics)
                    v = jax.lax.pmean(v, axes)
                else:
                    v = jax.lax.pmax(v, axes)
                fetches.append(v)
            return tuple(fetches), new_state

        return step

    def _compile(self, program, state_names, feed_names, fetch_names, mesh):
        step = self._traced_step(program, state_names, fetch_names, mesh)

        # ZeRO sharded buckets (distributed/sharding.py stages 1-3:
        # optimizer slots, gradient-merge shard accumulators, stage-3
        # param buckets): persistables declared at the GLOBAL padded
        # shape and marked dp_shard shard over "dp", so each rank holds
        # (and donates, and updates) only its slice.  Any dp degree
        # dividing the padded length runs the same program.  The specs
        # come from the partition-spec engine — the single consumption
        # point, so the engine's plan and the mesh's placement can never
        # drift apart.
        # dist_attr tp param sharding + accumulator inheritance live in
        # the engine too, so the per-dispatch and scanned compile paths
        # place identical 2-D layouts
        from .partition_spec import state_partition_specs
        state_specs = state_partition_specs(program, mesh, state_names)
        feed_specs = self._feed_specs(program, mesh, feed_names)
        fetch_specs = tuple(P() for _ in fetch_names)

        sharded = jax.shard_map(
            step, mesh=mesh, in_specs=(state_specs, feed_specs, P()),
            out_specs=(fetch_specs, state_specs), check_vma=False)
        return jax.jit(sharded, donate_argnums=(0,))
