"""Program IR verifier & distributed-correctness analyzer.

Analog of the reference's pre-execution validation
(/root/reference/paddle/fluid/framework/op_desc.cc OpDesc::Check +
per-op InferShape run by the C++ executor before launch) — but widened
to the invariants that actually break THIS framework: paddle_tpu stacks
five interacting program-rewrite passes (AMP, recompute, gradient_merge,
ZeRO-1 sharding, elastic fold) whose composition contracts were, until
now, enforced only by convention and caught only when an 8-device run
deadlocked or diverged.  This module moves those failures from chip
time to compile time, the same way `static/memory_analysis.py` moved
OOMs to estimator time.

`check_program(program, level=...)` walks the op IR and reports
structured `Diagnostic`s (never raises on a defect unless asked) at five
cumulative levels:

  1. ``graph``       — def-before-use, dangling vars, dtype/shape
                       consistency (via the same abstract evaluation as
                       `core/infer_shape.py`), feed/fetch/persistable
                       integrity, duplicate-write (SSA violation)
                       detection outside known accumulator patterns.
  2. ``collective``  — the SPMD/distributed checker: extracts the
                       ordered collective sequence, verifies
                       ring_id/dp_degree/shape/dtype agreement,
                       reduce-scatter↔allgather pairing, `dp_shard`
                       metadata consistency, control-flow-divergent
                       collectives (a collective under a data-dependent
                       sub-block = a guaranteed cross-rank deadlock
                       under shard_map), psum-reassociation hazards in
                       bitwise-order fold paths, double reductions, and
                       pass-composition order (the applied-passes
                       registry, `core/pass_framework.py`).
  3. ``donation``    — buffers donated to XLA (ZeRO slot shards,
                       elastic accumulators, the jitted step's donated
                       persistable state): alias-creating startup
                       assigns (double donation), reads-after-donation
                       (a forward/backward-role op reading state an
                       optimizer-role op already committed), fetches of
                       per-rank shards.
  4. ``retrace``     — lint for feeds whose shapes escape the batch-dim
                       bucketing policy and Python-captured array
                       constants baked into op attrs (each build
                       fingerprints differently → retrace every step).
  5. ``layout``      — the sharding-propagation analyzer
                       (static/layout_analysis.py): whole-graph SPMD
                       layout inference over the dp × mp mesh, V601-V605
                       (layout conflicts, missing reductions, redundant
                       reshards, mesh-axis disagreements, indivisible
                       shards) plus the priced reshard table.

Diagnostic codes are STABLE (docs/static_analysis.md): tests and
allowlists key on them.  Every diagnostic carries provenance (block/op
index, op type, op_uid, var name) so a report names the defect site,
not just the defect class.

`collective_sequence(program)` / `collective_wire_bytes(program, world)`
expose the ordered collective schedule and its ring-algorithm ICI cost —
the shared substrate the ROADMAP auto-parallel planner needs for
wire-byte costing.

Gating: ``PADDLE_TPU_VERIFY`` env ("" = off, "warn", "strict") arms
(a) a first-compile hook in `static/executor.py` /
`distributed/compiled_program.py` and (b) post-rewrite self-checks in
every rewrite pass; "strict" raises `ProgramVerificationError` on any
error-severity diagnostic.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..core.program import Block, OpDesc, OpRole, Program

__all__ = [
    "Diagnostic", "VerifyReport", "ProgramVerificationError",
    "check_program", "collective_sequence", "collective_wire_bytes",
    "entry_wire_bytes", "collective_wire_bytes_by_axis", "ring_axis",
    "program_ring_degrees",
    "verify_mode", "self_check", "verify_first_compile", "VERIFY_ENV",
]

VERIFY_ENV = "PADDLE_TPU_VERIFY"

# level name -> highest suite number it runs (levels are cumulative);
# 5 = the sharding-propagation layout analyzer (layout_analysis.py V6xx)
_LEVELS = {"graph": 1, "collective": 2, "donation": 3, "retrace": 4,
           "layout": 5, "all": 5, "strict": 5}

ERROR = "error"
WARNING = "warning"


class ProgramVerificationError(RuntimeError):
    """Raised by strict-mode verification when error diagnostics exist."""

    def __init__(self, report: "VerifyReport", context: str = ""):
        self.report = report
        head = f"program verification failed ({context})" if context \
            else "program verification failed"
        super().__init__(f"{head}:\n{report.render(errors_only=True)}")


class Diagnostic:
    """One structured finding with provenance."""

    __slots__ = ("code", "severity", "message", "block_idx", "op_idx",
                 "op_type", "op_uid", "var")

    def __init__(self, code: str, severity: str, message: str,
                 block_idx: Optional[int] = None,
                 op_idx: Optional[int] = None,
                 op_type: Optional[str] = None,
                 op_uid: Optional[int] = None,
                 var: Optional[str] = None):
        self.code = code
        self.severity = severity
        self.message = message
        self.block_idx = block_idx
        self.op_idx = op_idx
        self.op_type = op_type
        self.op_uid = op_uid
        self.var = var

    def where(self) -> str:
        parts = []
        if self.block_idx is not None:
            parts.append(f"block {self.block_idx}")
        if self.op_idx is not None:
            parts.append(f"op {self.op_idx}")
        if self.op_type:
            uid = f" uid={self.op_uid}" if self.op_uid is not None else ""
            parts.append(f"{self.op_type!r}{uid}")
        if self.var:
            parts.append(f"var {self.var!r}")
        return ", ".join(parts)

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}

    def __repr__(self):
        w = self.where()
        return f"[{self.code}/{self.severity}] {self.message}" + \
            (f"  ({w})" if w else "")


class VerifyReport:
    """All diagnostics from one `check_program` run."""

    def __init__(self, diagnostics: List[Diagnostic], level: str,
                 applied_passes: Optional[List[dict]] = None):
        self.diagnostics = list(diagnostics)
        self.level = level
        self.applied_passes = list(applied_passes or [])

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == ERROR]

    @property
    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == WARNING]

    @property
    def ok(self) -> bool:
        return not self.errors

    def codes(self) -> List[str]:
        return sorted({d.code for d in self.diagnostics})

    def by_code(self, code: str) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.code == code]

    def render(self, errors_only: bool = False) -> str:
        ds = self.errors if errors_only else self.diagnostics
        if not ds:
            return "clean (0 diagnostics)"
        return "\n".join(f"  {d!r}" for d in ds)

    def raise_on_error(self, context: str = ""):
        if self.errors:
            raise ProgramVerificationError(self, context)
        return self

    def __repr__(self):
        return (f"VerifyReport(level={self.level!r}, "
                f"errors={len(self.errors)}, "
                f"warnings={len(self.warnings)})")


# ---------------------------------------------------------------------------
# op-type classification tables
# ---------------------------------------------------------------------------
# cross-rank communication ops: must execute in the same order with the
# same operands on every rank or the mesh deadlocks / diverges
_COLLECTIVE_OPS = frozenset((
    "c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
    "c_allreduce_prod", "c_reducescatter", "c_allgather", "c_broadcast",
    "broadcast", "c_scatter", "c_concat", "c_split", "alltoall",
    "barrier", "mp_allreduce_sum", "c_elastic_fold", "partial_allgather",
    "p_send", "p_recv", "ring_attention", "sync_batch_norm",
    "sync_batch_norm_grad",
    # the Megatron f-operator's BACKWARD is an allreduce over the tensor
    # ring (ops/kernels/collective._c_identity_grad); grad ops inherit
    # the forward op's ring/mp stamps, so the schedule and the per-axis
    # wire pricer both see the mp ring's dominant backward cost
    "c_identity_grad",
))

# collectives whose summation order XLA may legally reassociate — fatal
# inside a path that requires a bitwise-stable reduction order (the
# elastic fold's whole contract, distributed/elastic.py)
_PSUM_ORDER_SENSITIVE = frozenset((
    "c_allreduce_sum", "c_reducescatter", "mp_allreduce_sum",
))

# output shapes depend on the mesh (off-mesh the kernels degrade to
# identity), so the abstract-evaluation shape check must skip them
_MESH_DEPENDENT_OPS = frozenset((
    "c_reducescatter", "c_allgather", "c_split", "c_concat", "c_scatter",
    "alltoall", "partial_allgather", "c_elastic_fold",
    "elastic_commit_mask", "scale_by_world_size", "ring_attention",
    "p_send", "p_recv",
))

# control-flow container ops: their sub-block carries run under traced
# lax control flow, where per-rank divergence is possible
_CONTROL_FLOW_OPS = frozenset((
    "while", "conditional_block", "cond", "static_rnn", "recurrent",
))

# in-place container writers: a tensor array var IS rebound by every
# write (write_to_array at index i), so multi-write is its contract,
# not an SSA violation
_INPLACE_CONTAINER_OPS = frozenset((
    "write_to_array", "array_write", "lod_tensor_to_array",
    "create_tensor_array",
))

# ops a reduction pass inserts between a collective and its consumer —
# shared vocabulary with distributed/compiled_program._grad_already_reduced
_REDUCE_TRANSPARENT = frozenset((
    "scale_by_world_size", "scale", "cast", "elementwise_add", "where",
    "reshape", "reshape2", "concat", "pad", "slice", "assign",
    "check_finite_and_unscale", "update_loss_scaling",
))
_REDUCE_OPS = frozenset(("c_allreduce_sum", "c_reducescatter",
                         "c_elastic_fold"))

_STARTUP_INIT_OPS = frozenset((
    "fill_constant", "uniform_random", "gaussian_random",
    "truncated_gaussian_random", "assign_value", "eye", "c_broadcast",
    "broadcast", "seed", "range", "linspace", "scale", "assign",
))


def _role(op: OpDesc) -> int:
    return int(op.attrs.get(OpRole.KEY, OpRole.Forward))


def _is_optimize_write(op: OpDesc) -> bool:
    return bool(_role(op) & OpRole.Optimize)


def _is_fwd_bwd_read(op: OpDesc) -> bool:
    # strip the Loss marker bit; Forward(0) and Backward(1) remain
    return (_role(op) & ~OpRole.Loss) in (OpRole.Forward, OpRole.Backward)


def _var_of(block: Block, name: str):
    try:
        return block.var(name)
    except KeyError:
        return None


def _numel(shape) -> Optional[int]:
    if shape is None:
        return None
    n = 1
    for d in shape:
        d = int(d)
        if d < 0:
            return None
        n *= d
    return n


def _dtype_bytes(dtype: Optional[str]) -> int:
    if not dtype:
        return 0
    from ..core.dtype import np_dtype
    try:
        return int(np.dtype(np_dtype(dtype)).itemsize)
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# collective-sequence extraction (the planner's wire-cost substrate)
# ---------------------------------------------------------------------------
def collective_sequence(program: Program) -> List[dict]:
    """The ordered cross-rank communication schedule of `program`.

    One entry per collective op, in execution order, with the operand
    metadata every rank must agree on (this IS the deadlock surface:
    under shard_map each rank traces the same op list, so any divergence
    in order/ring/shape means a rank waits on a collective its peers
    never post).  Entry keys: ``block``/``index`` (provenance),
    ``type``, ``ring_id``, ``dp_degree`` (None unless stamped),
    ``var``/``shape``/``dtype``/``nbytes`` (the X operand), ``op_uid``.

    This is also the substrate the ROADMAP auto-parallel planner costs
    ICI wire bytes over — see `collective_wire_bytes`.
    """
    seq = []
    for block in program.blocks:
        for i, op in enumerate(block.ops):
            if op.type not in _COLLECTIVE_OPS:
                continue
            xnames = op.inputs.get("X", []) or op.input_names()
            xname = xnames[0] if xnames else None
            v = _var_of(block, xname) if xname else None
            shape = tuple(v.shape) if v is not None and v.shape is not None \
                else None
            dtype = v.dtype if v is not None else None
            numel = _numel(shape)
            seq.append({
                "block": block.idx, "index": i, "type": op.type,
                "ring_id": int(op.attrs.get("ring_id", 0)),
                "dp_degree": (int(op.attrs["dp_degree"])
                              if op.attrs.get("dp_degree") else None),
                "var": xname, "shape": shape, "dtype": dtype,
                "nbytes": (numel * _dtype_bytes(dtype)
                           if numel is not None else None),
                "op_uid": op.attrs.get("op_uid"),
                # ZeRO stage stamps (distributed/sharding.py): stage the
                # pass emitted this op for and its role in the bucket
                # chain — the stage-aware pairing checks and the wire
                # pricer both read them
                "zero_stage": op.attrs.get("zero_stage"),
                "zero_role": op.attrs.get("zero_role"),
                # the X operand is a dp_shard persistable declared at the
                # GLOBAL padded shape: each rank's LOCAL operand is
                # 1/degree of the declared bytes (ZeRO-3 param gathers)
                "x_dp_shard": (int(v.attrs.get("dp_shard") or 0)
                               if v is not None else 0),
                # tensor-parallel builder stamps (distributed/
                # tensor_parallel.py): the model axis the op rides and
                # the tp degree declared at build time — the per-ring
                # wire pricer uses the degree, the layout analyzer the
                # axis
                "mp_axis": op.attrs.get("mp_axis"),
                "tp_degree": (int(op.attrs["tp_degree"])
                              if op.attrs.get("tp_degree") else None),
            })
    return seq


# default ring → mesh-axis binding: the shared canonicalizer table
# (core/mesh_axes.py — the same source CompiledProgram._get_mesh and
# layout_analysis speak, so analyzer and runtime can never disagree on
# the tensor axis's name)
from ..core.mesh_axes import RING_AXIS as _RING_AXIS
from ..core.mesh_axes import canonical_axis as _canonical_axis


def ring_axis(ring_id: int, mp_axis: Optional[str] = None) -> str:
    """The CANONICAL mesh-axis name a ring id binds to (``mp_axis``
    stamp wins; runtime spellings like ``"tp"`` canonicalize through
    `core.mesh_axes`; unknown rings render as ``ring<N>``)."""
    if mp_axis:
        return _canonical_axis(str(mp_axis))
    return _RING_AXIS.get(int(ring_id), f"ring{int(ring_id)}")


def _ring_degrees_from_seq(seq: List[dict]) -> Dict[int, int]:
    degrees: Dict[int, int] = {}
    for e in seq:
        d = e["tp_degree"] or e["dp_degree"]
        if d:
            degrees[e["ring_id"]] = max(degrees.get(e["ring_id"], 0),
                                        int(d))
    return degrees


def program_ring_degrees(program: Program) -> Dict[int, int]:
    """Per-ring group sizes the program's op stamps declare: the
    builders' ``tp_degree`` on the tensor ring, the sharding pass's
    ``dp_degree`` on ring 0.  The wire pricer's `ring_degrees` input —
    a non-dp ring must be priced at ITS degree, not the dp world.
    (Callers already holding a `collective_sequence` should derive the
    degrees from it instead of re-walking the program.)"""
    return _ring_degrees_from_seq(collective_sequence(program))


def _entry_nbytes(entry: dict, batch: Optional[int] = None) \
        -> Optional[int]:
    """An entry's operand bytes, optionally binding symbolic -1 dims to
    `batch`: the mp-ring collectives ride ACTIVATIONS ([-1, t, hidden]
    cotangents and partial sums), whose wire cost is batch-proportional
    and prices 0 unless the caller binds the batch."""
    n = entry.get("nbytes")
    if n:
        return n
    shape = entry.get("shape")
    if not batch or not shape:
        return None
    total = 1
    for d in shape:
        d = int(d)
        total *= int(batch) if d < 0 else d
    return total * _dtype_bytes(entry.get("dtype"))


def entry_wire_bytes(entry: dict, world: int,
                     ring_degrees: Optional[Dict[int, int]] = None,
                     batch: Optional[int] = None) -> float:
    """Ring-algorithm ICI bytes ONE rank moves for a single
    `collective_sequence` entry: allreduce 2(N-1)/N of the buffer,
    reduce-scatter (N-1)/N, allgather and the elastic all-gather fold
    (N-1)× the local shard, broadcast/scatter (N-1)/N, alltoall
    (N-1)/N.  Group-size resolution, most specific first: the entry's
    own ``dp_degree``/``tp_degree`` stamp (the pass that emitted the op
    recorded the group it rewrote for), then ``ring_degrees`` (ring id →
    size, e.g. `program_ring_degrees` or a planner's candidate mesh),
    then `world` — so a tensor-ring collective on a 4×2 mesh prices at
    its mp degree 2, never the dp world.  `batch` binds symbolic -1
    dims so activation collectives (the mp ring's whole traffic) price
    their batch-proportional bytes; unknown sizes price 0.
    Shared by `collective_wire_bytes` and the auto-parallel planner's
    overlap-aware roofline (static/planner.py)."""
    n = _entry_nbytes(entry, batch)
    if not n:
        return 0.0
    g = (entry["dp_degree"] or entry.get("tp_degree") or
         (ring_degrees or {}).get(entry["ring_id"]) or world)
    if g <= 1:
        return 0.0
    t = entry["type"]
    if t in ("c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
             "c_allreduce_prod", "mp_allreduce_sum", "sync_batch_norm",
             "sync_batch_norm_grad", "c_identity_grad"):
        # c_identity_grad: the Megatron f-operator's backward psum of
        # the replicated input's cotangent over the tensor ring
        return 2.0 * (g - 1) / g * n
    if t in ("c_reducescatter", "c_scatter", "c_broadcast",
             "broadcast", "alltoall"):
        return (g - 1) / g * n
    if t in ("c_allgather", "c_concat", "c_elastic_fold",
             "partial_allgather"):
        # input is the local shard; the ring moves (g-1) remote shards
        # (c_concat's kernel IS a tiled all_gather, ops/kernels/
        # collective.py).  When the operand is DECLARED at the GLOBAL
        # gathered shape — a ZeRO-3 dp_shard param bucket, or a
        # tensor-ring gather whose builder keeps build-time shapes
        # global (``mp_axis`` stamp) — the local shard is 1/g of the
        # declared bytes.
        if entry.get("x_dp_shard") or entry.get("mp_axis"):
            return (g - 1) / g * n
        return float((g - 1) * n)
    if t in ("p_send", "p_recv"):
        return float(n)
    # c_split is a LOCAL dynamic slice of a replicated operand (each
    # rank keeps its own piece — ops/kernels/collective.py): zero wire.
    # barrier / elastic_commit_mask / ring_attention: control traffic
    # only (ring_attention's K/V rotation is its own op-internal story).
    return 0.0


def collective_wire_bytes(program: Program, world: int,
                          ring_id: Optional[int] = None,
                          ring_degrees: Optional[Dict[int, int]] = None,
                          batch: Optional[int] = None) -> int:
    """ICI bytes ONE rank moves per step under ring-algorithm accounting
    (per-entry formulas: `entry_wire_bytes`).  Entries with unknown
    sizes contribute 0 (count them via `collective_sequence` if that
    matters; `batch` binds symbolic -1 dims so activation collectives
    price).  `ring_id=None` sums every ring; `ring_degrees` maps ring
    id → that ring's OWN group size (default: the program's stamps via
    `program_ring_degrees`) so non-dp rings never price at the dp
    world."""
    if world <= 1:
        return 0
    seq = collective_sequence(program)
    if ring_degrees is None:
        ring_degrees = _ring_degrees_from_seq(seq)
    total = 0.0
    for e in seq:
        if ring_id is not None and e["ring_id"] != ring_id:
            continue
        total += entry_wire_bytes(e, world, ring_degrees, batch)
    return int(total)


def collective_wire_bytes_by_axis(program: Program, world: int,
                                  ring_degrees: Optional[Dict[int, int]]
                                  = None,
                                  batch: Optional[int] = None
                                  ) -> Dict[str, int]:
    """Per-mesh-axis split of `collective_wire_bytes`: ring-accounted
    ICI bytes one rank moves per step, keyed by the axis each ring binds
    to (`ring_axis`: ring 0 → "dp", the tensor ring → "mp", the
    sequence ring → "sp").  The 2-D planner's wire substrate — an
    mp-ring byte overlaps different hardware links than a dp-ring byte,
    so the roofline must see them separately.  `batch` binds symbolic -1
    dims (the mp ring's traffic is activations)."""
    seq = collective_sequence(program)
    if ring_degrees is None:
        ring_degrees = _ring_degrees_from_seq(seq)
    totals: Dict[str, float] = {}
    if world <= 1 and not ring_degrees:
        return {}
    for e in seq:
        axis = ring_axis(e["ring_id"], e.get("mp_axis"))
        totals[axis] = totals.get(axis, 0.0) + \
            entry_wire_bytes(e, world, ring_degrees, batch)
    return {a: int(b) for a, b in sorted(totals.items())}


# ---------------------------------------------------------------------------
# suite 1: graph verifier
# ---------------------------------------------------------------------------
def _check_graph(program: Program, fetch_roots: Set[str],
                 out: List[Diagnostic]):
    from ..ops.registry import get_op_info
    block = program.global_block()

    # V109 unknown ops (all blocks): the executor would hit the same
    # NotImplementedError mid-trace; catching it here names the op site
    for b in program.blocks:
        for i, op in enumerate(b.ops):
            if op.type in ("feed", "fetch"):
                continue
            if get_op_info(op.type) is None:
                out.append(Diagnostic(
                    "V109", ERROR,
                    f"op type {op.type!r} has no registered kernel",
                    block_idx=b.idx, op_idx=i, op_type=op.type,
                    op_uid=op.attrs.get("op_uid")))

    # availability walk over the global block (sub-blocks close over the
    # whole parent env at trace time, so def-before-use is only
    # well-defined at the top level)
    available: Set[str] = set()
    for b in program.blocks:
        for v in b.vars.values():
            if v.persistable or v.is_data:
                available.add(v.name)
    def _required_inputs(op: OpDesc) -> List[str]:
        """Input names excluding OPTIONAL slots: the tracer hands a
        kernel None for a missing optional operand by contract (e.g.
        heter_recv's Dummy dependency token), so only required slots
        constitute a real read."""
        info = get_op_info(op.type)
        if info is None:
            return op.input_names()
        names = []
        for slot in info.inputs:
            if slot.optional:
                continue
            names.extend(op.inputs.get(slot.name, []))
        # names in slots the registry doesn't declare still count
        declared = {s.name for s in info.inputs}
        for slot_name, vs in op.inputs.items():
            if slot_name not in declared:
                names.extend(vs)
        return names

    writers: Dict[str, List[Tuple[int, OpDesc]]] = {}
    for i, op in enumerate(block.ops):
        if op.type == "feed":
            available.update(op.output_names())
            continue
        if op.type != "fetch":
            for n in _required_inputs(op):
                if n and n not in available and not block.has_var(n):
                    # read of a name that is neither produced, declared,
                    # persistable, nor a feed — the trace would KeyError
                    out.append(Diagnostic(
                        "V101", ERROR,
                        f"op reads {n!r} before any definition (not a "
                        f"feed, not persistable, no producing op)",
                        block_idx=0, op_idx=i, op_type=op.type,
                        op_uid=op.attrs.get("op_uid"), var=n))
                elif n and n not in available:
                    # declared but never produced: only an error when it
                    # cannot be fed (a declared non-data temp with no
                    # producer is a broken rewrite)
                    v = _var_of(block, n)
                    if v is not None and not v.is_data and \
                            not v.persistable:
                        out.append(Diagnostic(
                            "V101", ERROR,
                            f"op reads {n!r} before its definition — "
                            f"declared but no earlier op produces it",
                            block_idx=0, op_idx=i, op_type=op.type,
                            op_uid=op.attrs.get("op_uid"), var=n))
        for n in op.output_names():
            if not n:
                continue
            available.add(n)
            writers.setdefault(n, []).append((i, op))

    # V106 duplicate write (SSA violation) outside accumulator patterns:
    # persistables are the sanctioned in-place state (counters, params,
    # masked commits); control-flow carries are rewritten in place by
    # design; everything else must be single-assignment
    for n, ws in writers.items():
        if len(ws) < 2:
            continue
        v = _var_of(block, n)
        if v is not None and (v.persistable or v.is_data):
            continue
        if any(op.type in _CONTROL_FLOW_OPS or
               op.type in _INPLACE_CONTAINER_OPS for _, op in ws):
            continue
        i, op = ws[1]
        out.append(Diagnostic(
            "V106", WARNING,
            f"non-persistable var {n!r} is written by {len(ws)} ops "
            f"(SSA violation outside the known accumulator patterns); "
            f"later reads silently see the last write",
            block_idx=0, op_idx=i, op_type=op.type,
            op_uid=op.attrs.get("op_uid"), var=n))

    # V102 dangling @GRAD vars.  Scoped to gradients in a TRAINING
    # program (one with optimizer ops): there every produced gradient
    # must reach an optimizer/reduction consumer, so a dead one means a
    # rewrite dropped the consumer.  Deliberately NOT a general
    # dead-code lint — unfetched forward metrics and `gradients()` API
    # leaves are user intent (and DCE's job), not defects.
    consumed: Set[str] = set()
    for b in program.blocks:
        for op in b.ops:
            consumed.update(n for n in op.input_names() if n)
    has_optimizer = any(_is_optimize_write(op) and "Grad" in op.inputs
                        for op in block.ops)
    if has_optimizer:
        for i, op in enumerate(block.ops):
            if op.type in ("feed", "fetch"):
                continue
            info = get_op_info(op.type)
            if info is not None and info.side_effect:
                continue
            outs = [n for n in op.output_names() if n]
            if not outs or not all(n.endswith("@GRAD") for n in outs):
                continue
            live = any(
                n in consumed or n in fetch_roots or (
                    (v := _var_of(block, n)) is not None
                    and (v.persistable or v.is_data))
                for n in outs)
            if not live:
                out.append(Diagnostic(
                    "V102", WARNING,
                    f"gradient var(s) {outs} dangle: produced but "
                    f"consumed by no optimizer/reduction op in a "
                    f"training program (a rewrite dropped the consumer)",
                    block_idx=0, op_idx=i, op_type=op.type,
                    op_uid=op.attrs.get("op_uid"), var=outs[0]))

    # V107 feed/fetch integrity
    for b in program.blocks:
        for v in b.vars.values():
            if v.is_data and v.persistable:
                out.append(Diagnostic(
                    "V107", ERROR,
                    f"var {v.name!r} is both feed data and persistable: "
                    f"it would be fed AND donated as jitted state in the "
                    f"same step", block_idx=b.idx, var=v.name))
    for i, op in enumerate(block.ops):
        if op.type in ("feed", "fetch"):
            continue
        for n in op.output_names():
            v = _var_of(block, n) if n else None
            if v is not None and v.is_data:
                out.append(Diagnostic(
                    "V107", ERROR,
                    f"op overwrites feed var {n!r}; the next step's feed "
                    f"would silently clobber (or be clobbered by) it",
                    block_idx=0, op_idx=i, op_type=op.type,
                    op_uid=op.attrs.get("op_uid"), var=n))
    for n in fetch_roots:
        if not block.has_var(n) and n not in available:
            out.append(Diagnostic(
                "V107", ERROR,
                f"fetch target {n!r} exists nowhere in the program",
                var=n))

    _check_shapes(program, out)


def _check_shapes(program: Program, out: List[Diagnostic]):
    """V103/V104: re-derive each op's output shape/dtype by the same
    abstract evaluation `core/infer_shape.py` uses at build time and
    compare against the DECLARED VarDescs.  Catches pass-emitted ops
    whose hand-declared temps disagree with the kernel (a dtype clash
    the trace would only surface as a deep XLA error, or a shape clash
    that silently broadcasts).  Mesh-dependent ops are skipped (their
    off-mesh degraded shapes differ by design), as are grad ops (their
    cotangent slot convention makes abstract evaluation ambiguous here —
    build-time infer_shape already covered them)."""
    import jax
    from ..core.infer_shape import _struct_for, _SENTINEL
    from ..ops.registry import get_op_info, OpContext

    block = program.global_block()
    for i, op in enumerate(block.ops):
        if op.type in ("feed", "fetch") or op.type in _MESH_DEPENDENT_OPS \
                or op.type in _CONTROL_FLOW_OPS \
                or op.type.endswith("_grad"):
            continue
        if op.attrs.get("zero_sharded") or any(
                (v := _var_of(block, n)) is not None
                and v.attrs.get("dp_shard")
                for n in op.input_names() + op.output_names() if n):
            # sharded bucket update: slot operands are declared at the
            # GLOBAL padded shape but each rank traces its 1/N slice
            # under shard_map — off-mesh abstract shapes differ by design
            continue
        info = get_op_info(op.type)
        if info is None:
            continue  # V109 already reported
        ins = {}
        complete = True
        for slot in info.inputs:
            names = op.inputs.get(slot.name, [])
            if not names:
                if not slot.optional:
                    complete = False
                    break
                ins[slot.name] = [] if slot.duplicable else None
                continue
            try:
                structs = [_struct_for(block.var(n)) for n in names if n]
            except (KeyError, NotImplementedError):
                complete = False
                break
            ins[slot.name] = structs if slot.duplicable else structs[0]
        if not complete:
            continue
        try:
            if info.infer_shape is not None:
                outs = info.infer_shape(ins, op.attrs)
            else:
                ctx = OpContext(seed=0)
                outs = jax.eval_shape(
                    lambda i_: info.kernel(i_, op.attrs, ctx), ins)
        except Exception:
            continue  # kernel refused the abstract operands; not a verdict
        if not isinstance(outs, dict):
            continue
        for slot in info.outputs:
            names = op.outputs.get(slot.name, [])
            res = outs.get(slot.name)
            if not names or res is None:
                continue
            res_list = res if isinstance(res, (list, tuple)) else [res]
            for name, st in zip(names, res_list):
                if not name or st is None or not hasattr(st, "shape"):
                    continue
                v = _var_of(block, name)
                if v is None:
                    continue
                inferred_shape = tuple(-1 if s == _SENTINEL else int(s)
                                       for s in st.shape)
                inferred_dtype = str(np.dtype(st.dtype).name) \
                    if hasattr(st, "dtype") else None
                if v.dtype is not None and inferred_dtype is not None \
                        and v.dtype != inferred_dtype:
                    out.append(Diagnostic(
                        "V103", ERROR,
                        f"declared dtype {v.dtype} of {name!r} clashes "
                        f"with the kernel's inferred {inferred_dtype}",
                        block_idx=0, op_idx=i, op_type=op.type,
                        op_uid=op.attrs.get("op_uid"), var=name))
                if v.shape is not None:
                    declared = tuple(int(s) for s in v.shape)
                    if len(declared) != len(inferred_shape) or any(
                            d >= 0 and s >= 0 and d != s
                            for d, s in zip(declared, inferred_shape)):
                        out.append(Diagnostic(
                            "V104", ERROR,
                            f"declared shape {list(declared)} of {name!r} "
                            f"clashes with the kernel's inferred "
                            f"{list(inferred_shape)}",
                            block_idx=0, op_idx=i, op_type=op.type,
                            op_uid=op.attrs.get("op_uid"), var=name))


# ---------------------------------------------------------------------------
# suite 2: SPMD / collective checker
# ---------------------------------------------------------------------------
def _check_collectives(program: Program, out: List[Diagnostic]):
    seq = collective_sequence(program)
    block = program.global_block()

    # V205: a collective inside a control-flow sub-block.  Under
    # shard_map every rank traces the same op list, but a sub-block runs
    # under lax.while_loop/cond whose predicate is DATA — per-rank data
    # diverges, so one rank can take an iteration (and post a collective)
    # its peers never reach: a guaranteed deadlock on a real mesh.
    for e in seq:
        if e["block"] != 0:
            out.append(Diagnostic(
                "V205", ERROR,
                f"collective {e['type']!r} inside control-flow sub-block "
                f"{e['block']}: a rank-divergent trip count deadlocks "
                f"the mesh (hoist the collective out of the loop/branch)",
                block_idx=e["block"], op_idx=e["index"],
                op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))

    # V202a: dp_degree consensus on each ring (the sharding pass stamps
    # the world it padded buckets for — two degrees on one ring means
    # two passes rewrote for different worlds)
    ring_degrees: Dict[int, Set[int]] = {}
    for e in seq:
        if e["dp_degree"] is not None:
            ring_degrees.setdefault(e["ring_id"], set()).add(e["dp_degree"])
    for ring, degs in ring_degrees.items():
        if len(degs) > 1:
            out.append(Diagnostic(
                "V202", ERROR,
                f"collectives on ring {ring} disagree on dp_degree "
                f"{sorted(degs)}: the program was rewritten for two "
                f"different worlds", var=None))

    # V203: per-op operand consistency for degree-stamped shard ops
    for e in seq:
        if e["type"] not in ("c_reducescatter", "c_allgather") or \
                e["dp_degree"] is None:
            continue
        d = e["dp_degree"]
        op = program.blocks[e["block"]].ops[e["index"]]
        in_v = _var_of(block, e["var"]) if e["var"] else None
        out_names = op.outputs.get("Out", [])
        out_v = _var_of(block, out_names[0]) if out_names else None
        in_n = _numel(in_v.shape) if in_v is not None else None
        out_n = _numel(out_v.shape) if out_v is not None else None
        if in_v is not None and out_v is not None and \
                in_v.dtype and out_v.dtype and in_v.dtype != out_v.dtype:
            out.append(Diagnostic(
                "V203", ERROR,
                f"{e['type']} input dtype {in_v.dtype} != output dtype "
                f"{out_v.dtype} (collectives preserve dtype; cast "
                f"separately)", block_idx=e["block"], op_idx=e["index"],
                op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))
        if e["type"] == "c_reducescatter" and in_n is not None:
            if in_n % d != 0:
                out.append(Diagnostic(
                    "V203", ERROR,
                    f"c_reducescatter input numel {in_n} is not divisible "
                    f"by dp_degree {d}: the shard split is ill-formed",
                    block_idx=e["block"], op_idx=e["index"],
                    op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))
            elif out_n is not None and out_n != in_n // d:
                out.append(Diagnostic(
                    "V203", ERROR,
                    f"c_reducescatter output numel {out_n} != input "
                    f"{in_n} / dp_degree {d}",
                    block_idx=e["block"], op_idx=e["index"],
                    op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))
        if e["type"] == "c_allgather" and in_n is not None and \
                out_n is not None:
            if e.get("x_dp_shard"):
                # ZeRO-3 JIT gather: the operand is DECLARED at the
                # global padded shape (each rank's traced slice is 1/d),
                # so the gathered output must equal the declared input
                if out_n != in_n:
                    out.append(Diagnostic(
                        "V203", ERROR,
                        f"c_allgather of dp_shard var: output numel "
                        f"{out_n} != the bucket's declared global numel "
                        f"{in_n}",
                        block_idx=e["block"], op_idx=e["index"],
                        op_type=e["type"], op_uid=e["op_uid"],
                        var=e["var"]))
            elif out_n != in_n * d:
                out.append(Diagnostic(
                    "V203", ERROR,
                    f"c_allgather output numel {out_n} != input {in_n} × "
                    f"dp_degree {d}",
                    block_idx=e["block"], op_idx=e["index"],
                    op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))

    # V201/V202b: reduce-scatter ↔ allgather pairing with matching
    # bucket plans, validated AGAINST THE RECORDED STAGE.  The ZeRO-1/2
    # recipe is rs(bucket) → sharded update → ag(shard): every
    # degree-stamped rs must be followed by an ag whose local operand is
    # the same shard length, on the same ring.  ZeRO-3 changes both
    # halves: a JIT param gather (``zero_role`` gather_fwd/gather_bwd)
    # is not a publish — it must read a dp_shard param bucket — and the
    # grad reduce-scatter's "gathered counterpart" is the NEXT step's
    # forward gather, so instead of an ag pairing the rs must reach (via
    # pass-inserted plumbing — the gradient-merge shard accumulator
    # included) a ``zero_sharded`` update writing a dp_shard bucket in
    # place.  Pair the rest greedily in program order by shard numel;
    # ring mismatches on an otherwise-matching pair get the sharper
    # V202.
    block0 = program.global_block()
    consumers: Dict[str, List[OpDesc]] = {}
    for op in block0.ops:
        for n in op.input_names():
            if n:
                consumers.setdefault(n, []).append(op)

    def _reaches_inplace_sharded_update(rs_entry) -> bool:
        """rs output → (transparent plumbing)* → op with `zero_sharded`
        whose ParamOut is a dp_shard var (the ZeRO-3 in-place bucket
        update — the structural witness that the publish is deferred to
        the next step's gather)."""
        op0 = program.blocks[rs_entry["block"]].ops[rs_entry["index"]]
        frontier = [n for n in op0.outputs.get("Out", []) if n]
        seen: Set[str] = set()
        hops = 64
        while frontier and hops > 0:
            hops -= 1
            n = frontier.pop()
            if n in seen:
                continue
            seen.add(n)
            for c in consumers.get(n, ()):
                if c.attrs.get("zero_sharded"):
                    pouts = c.outputs.get("ParamOut", [])
                    pv = _var_of(block0, pouts[0]) if pouts else None
                    if pv is not None and pv.attrs.get("dp_shard"):
                        return True
                    # under gradient_merge the update's ParamOut is a
                    # @MASKED temp and the bucket write is the deferred
                    # where(mask, temp, bucket) commit — follow it
                    for w in consumers.get(pouts[0] if pouts else "", ()):
                        if w.type != "where":
                            continue
                        wouts = w.outputs.get("Out", [])
                        wv = _var_of(block0, wouts[0]) if wouts else None
                        if wv is not None and wv.attrs.get("dp_shard"):
                            return True
                    continue
                if c.type in _REDUCE_TRANSPARENT or \
                        c.type in ("elementwise_add", "scale", "where"):
                    frontier.extend(m for m in c.output_names() if m)
        return False

    rs_open: List[dict] = []
    for e in seq:
        if e["dp_degree"] is None:
            continue
        if e["type"] == "c_reducescatter":
            if e.get("zero_stage") == 3 and \
                    _reaches_inplace_sharded_update(e):
                # deferred publish: the sharded update writes the param
                # bucket in place; the next step's JIT gather is the ag
                continue
            d = e["dp_degree"]
            n = _numel(e["shape"])
            e["_shard"] = (n // d) if (n is not None and d and
                                       n % d == 0) else None
            rs_open.append(e)
        elif e["type"] == "c_allgather":
            if e.get("zero_role") in ("gather_fwd", "gather_bwd"):
                # ZeRO-3 JIT param gather: never part of the publish
                # pairing, but it must actually read sharded state — a
                # gather of a replicated var would move (g-1)× the full
                # params over ICI for nothing
                if not e.get("x_dp_shard"):
                    out.append(Diagnostic(
                        "V201", ERROR,
                        f"ZeRO-3 JIT param gather reads {e['var']!r}, "
                        f"which is not a dp_shard-marked bucket: the "
                        f"gather would replicate an already-replicated "
                        f"buffer (stage stamp disagrees with the "
                        f"program's sharded state)",
                        block_idx=e["block"], op_idx=e["index"],
                        op_type=e["type"], op_uid=e["op_uid"],
                        var=e["var"]))
                continue
            n = _numel(e["shape"])  # ag input IS the local shard
            match = next((r for r in rs_open if r["_shard"] is not None
                          and r["_shard"] == n), None)
            if match is None:
                out.append(Diagnostic(
                    "V201", ERROR,
                    f"c_allgather (shard numel {n}) has no preceding "
                    f"unpaired c_reducescatter with a matching bucket "
                    f"plan — swapped collective order or an orphaned "
                    f"publish (every rank would gather stale shards)",
                    block_idx=e["block"], op_idx=e["index"],
                    op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))
            else:
                rs_open.remove(match)
                if match["ring_id"] != e["ring_id"]:
                    out.append(Diagnostic(
                        "V202", ERROR,
                        f"paired c_reducescatter (ring {match['ring_id']}) "
                        f"and c_allgather (ring {e['ring_id']}) ride "
                        f"different rings: the publish gathers over a "
                        f"different device group than the reduction",
                        block_idx=e["block"], op_idx=e["index"],
                        op_type=e["type"], op_uid=e["op_uid"],
                        var=e["var"]))
    for r in rs_open:
        if r.get("zero_stage") == 3:
            out.append(Diagnostic(
                "V201", ERROR,
                f"ZeRO-3 c_reducescatter (bucket {r['var']!r}) reaches "
                f"no in-place sharded update of a dp_shard param bucket "
                f"and no publish allgather: the reduced gradients go "
                f"nowhere (the deferred-publish contract is broken)",
                block_idx=r["block"], op_idx=r["index"], op_type=r["type"],
                op_uid=r["op_uid"], var=r["var"]))
            continue
        out.append(Diagnostic(
            "V201", ERROR,
            f"c_reducescatter (bucket {r['var']!r}) is never published "
            f"back by a matching c_allgather: params stay stale on "
            f"{max((r['dp_degree'] or 2) - 1, 1)} of "
            f"{r['dp_degree']} ranks",
            block_idx=r["block"], op_idx=r["index"], op_type=r["type"],
            op_uid=r["op_uid"], var=r["var"]))

    # V204: dp_shard metadata consistency — degree AND stage.  Every op
    # the sharding pass emitted is stamped with the stage it was emitted
    # for; the recorded plan is the authority, and a disagreement means
    # the program was rewritten twice for different stages (or a stamp
    # was hand-edited) — the stage-aware V201/V203 rules above would
    # then be validating against the wrong contract.
    plan = getattr(program, "_zero_shard_plan", None)
    plan_degree = int(plan.dp_degree) if plan is not None and \
        getattr(plan, "buckets", None) else None
    plan_stage = int(getattr(plan, "stage", 1)) if plan is not None and \
        getattr(plan, "buckets", None) else None
    if plan_stage is not None:
        stamped_stages = {int(op.attrs["zero_stage"])
                          for b in program.blocks for op in b.ops
                          if op.attrs.get("zero_stage") is not None}
        for s in sorted(stamped_stages - {plan_stage}):
            out.append(Diagnostic(
                "V204", ERROR,
                f"ops stamped zero_stage={s} disagree with the recorded "
                f"ShardingPlan stage={plan_stage}: the program carries "
                f"two different ZeRO rewrites (or a stamp was edited) — "
                f"stage-aware collective validation is unsound"))
        has_pbucket = any(v.attrs.get("zero_param_bucket")
                          for b in program.blocks for v in b.vars.values())
        if has_pbucket and plan_stage < 3:
            out.append(Diagnostic(
                "V204", ERROR,
                f"a ZeRO-3 param bucket var exists but the recorded plan "
                f"says stage={plan_stage}: parameters are sharded without "
                f"the stage-3 gather/update contract on record"))
    stamped = {d for degs in ring_degrees.values() for d in degs}
    for b in program.blocks:
        for v in b.vars.values():
            ds = v.attrs.get("dp_shard")
            if not ds:
                continue
            ds = int(ds)
            if v.shape and int(v.shape[0]) % ds != 0:
                out.append(Diagnostic(
                    "V204", ERROR,
                    f"dp_shard({ds}) var {v.name!r} has leading dim "
                    f"{v.shape[0]} not divisible by the shard degree",
                    block_idx=b.idx, var=v.name))
            if plan_degree is not None and ds != plan_degree:
                out.append(Diagnostic(
                    "V204", ERROR,
                    f"dp_shard({ds}) var {v.name!r} disagrees with the "
                    f"program's ShardingPlan dp_degree={plan_degree}",
                    block_idx=b.idx, var=v.name))
            elif plan_degree is None and stamped and ds not in stamped:
                out.append(Diagnostic(
                    "V204", ERROR,
                    f"dp_shard({ds}) var {v.name!r} disagrees with the "
                    f"collectives' stamped dp_degree {sorted(stamped)}",
                    block_idx=b.idx, var=v.name))

    # V206: psum-reassociation hazard inside a bitwise-order fold path.
    # The elastic fold exists BECAUSE psum's reduction order is
    # implementation-defined; any order-sensitive psum collective on the
    # fold's ring silently re-introduces the world-size dependence.
    el_meta = getattr(program, "_elastic_meta", None)
    if el_meta is not None:
        for e in seq:
            if e["type"] in _PSUM_ORDER_SENSITIVE and e["ring_id"] == 0:
                if el_meta.get("zero_stage1") and e.get("zero_role"):
                    # elastic × ZeRO-1: the bucket reduce-scatter IS the
                    # composition's documented reduction — it trades the
                    # bitwise cross-topology contract for allclose
                    # (distributed/elastic.py), so it is not a latent
                    # reassociation hazard
                    continue
                out.append(Diagnostic(
                    "V206", ERROR,
                    f"{e['type']} on ring 0 inside an elastic program: "
                    f"psum order is implementation-defined, breaking the "
                    f"fold's bitwise topology invariance (reduce through "
                    f"c_elastic_fold instead)",
                    block_idx=e["block"], op_idx=e["index"],
                    op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))

    # V207: double reduction — a reduction collective whose operand's
    # producer chain (through pass-inserted plumbing only) already
    # contains a reduction.  The idempotency contract
    # insert_grad_allreduce/shard_optimizer_states maintain by hand.
    producers: Dict[str, OpDesc] = {}
    for op in block.ops:
        for n in op.output_names():
            if n:
                producers[n] = op
    for i, op in enumerate(block.ops):
        if op.type not in _REDUCE_OPS:
            continue
        if op.type == "c_elastic_fold" and op.attrs.get("pre_reduced"):
            # elastic × ZeRO-1 window accumulation: X IS the bucket's
            # reduce-scattered shard by design — the fold skips its
            # gather half and only continues the accumulator
            continue
        frontier = [n for n in op.inputs.get("X", []) if n]
        seen: Set[str] = set()
        hops = 64
        while frontier and hops > 0:
            hops -= 1
            n = frontier.pop()
            if n in seen:
                continue
            seen.add(n)
            p = producers.get(n)
            if p is None or p is op:
                continue
            if p.type in _REDUCE_OPS:
                out.append(Diagnostic(
                    "V207", ERROR,
                    f"{op.type} re-reduces {n!r}, already reduced by "
                    f"{p.type} upstream: gradients would be scaled/"
                    f"summed twice (a reduction pass was applied twice)",
                    block_idx=0, op_idx=i, op_type=op.type,
                    op_uid=op.attrs.get("op_uid"), var=n))
                break
            if p.type in _REDUCE_TRANSPARENT:
                frontier.extend(p.input_names())

    # V208: a per-micro-step collective the scanned-window hoist would
    # have removed.  A gradient-merge program's publish-role collectives
    # (the ZeRO allgather after the masked commit) run under the merge
    # MASK — K-1 of every K dispatches move those bytes to publish
    # values the mask then discards.  The commit-tail hoist
    # (distributed/scan_window.mark_scan_hoist + Executor.run_steps)
    # runs them once per window instead; warn when the program merged
    # gradients but nothing recorded the hoist.  Keyed off the gm mask
    # (gm_role stamps / _gm_meta) + the publish zero_role stamps, so a
    # hand-built masked commit without the stamps stays silent rather
    # than false-positive.
    from ..core.pass_framework import has_applied
    gm_meta = getattr(program, "_gm_meta", None) or {}
    if int(gm_meta.get("k", 0) or 0) > 1 and \
            not has_applied(program, "scan_hoist"):
        has_mask = any(op.attrs.get("gm_role") == "mask"
                       for op in block.ops)
        for e in seq:
            if e.get("zero_role") == "publish" and has_mask:
                out.append(Diagnostic(
                    "V208", WARNING,
                    f"{e['type']} publishes under a gradient-merge mask "
                    f"(K={gm_meta['k']}): {gm_meta['k'] - 1} of every "
                    f"{gm_meta['k']} dispatches move these bytes for a "
                    f"masked-out commit — the scanned-window hoist "
                    f"(scan_window.mark_scan_hoist / run_steps) "
                    f"publishes once per window",
                    block_idx=e["block"], op_idx=e["index"],
                    op_type=e["type"], op_uid=e["op_uid"], var=e["var"]))

    _check_pass_order(program, out)


def _check_pass_order(program: Program, out: List[Diagnostic]):
    """V501-V503: composition contracts between the rewrite passes, and
    V504: plan drift — the program's actually-applied passes disagree
    with the auto-parallel plan recorded on it.  Both read the
    applied-passes registry (core/pass_framework.py)."""
    from ..core.pass_framework import applied_passes
    entries = applied_passes(program)
    order = [e["pass"] for e in entries]
    if "elastic" in order and "gradient_merge" in order:
        out.append(Diagnostic(
            "V501", ERROR,
            "elastic and gradient_merge both applied: the elastic "
            "schedule IS a masked accumulation window — stacking a "
            "second counter double-masks the optimizer commit"))
    el_meta = getattr(program, "_elastic_meta", None) or {}
    if "elastic" in order and "zero1_sharding" in order:
        if order.index("zero1_sharding") > order.index("elastic"):
            out.append(Diagnostic(
                "V503", ERROR,
                "zero1_sharding applied AFTER elastic: the sharding "
                "pass would bucket the fold's @MASKED temps — "
                "elasticize must run on the already-sharded program"))
        elif not el_meta.get("zero_stage1"):
            out.append(Diagnostic(
                "V503", ERROR,
                "elastic and zero1_sharding both applied but the "
                "elastic rewrite was not ZeRO-aware (no sharded window "
                "accumulators): the ordered fold reduces into "
                "REPLICATED accumulators while ZeRO-1 updates 1/N "
                "shards — re-run elasticize on the sharded program"))
    if "gradient_merge" in order and "zero1_sharding" in order and \
            order.index("gradient_merge") < order.index("zero1_sharding"):
        out.append(Diagnostic(
            "V502", ERROR,
            "zero1_sharding applied AFTER gradient_merge: sharding must "
            "run first so the masked commit wraps the bucketed sharded "
            "update (the reverse buckets the @MASKED temps and "
            "reduce-scatters every micro-step's partial sums)"))

    # V504: plan drift.  `static.plan_program`/`apply_plan` record the
    # chosen knobs as an "auto_parallel_plan" registry entry; the
    # rewrites the plan names record their own entries when applied.
    # A program whose ACTUAL rewrite state (remat / dp_shard degree /
    # gradient_merge K / ring op presence / shard bucket size) disagrees
    # with the recorded plan was hand-edited after planning — its bench
    # records and docs would attribute the numbers to knobs that never
    # ran.
    plans = [e for e in entries if e.get("pass") == "auto_parallel_plan"]
    if plans:
        plan = plans[-1]  # latest plan is the authority

        def _drift(knob, planned, applied):
            out.append(Diagnostic(
                "V504", ERROR,
                f"plan drift: recorded auto-parallel plan says "
                f"{knob}={planned!r} but the program's applied passes "
                f"say {applied!r} — the program was modified after "
                f"planning (re-plan, or apply the recorded plan)"))

        remat_applied = "recompute" in order
        if "remat" in plan and bool(plan["remat"]) != remat_applied:
            _drift("remat", bool(plan["remat"]), remat_applied)
        zs = next((e for e in reversed(entries)
                   if e["pass"] == "zero1_sharding"), None)
        dp_applied = int(zs.get("dp_degree", 0)) if zs else 0
        if "dp_shard" in plan and int(plan["dp_shard"] or 0) != dp_applied:
            _drift("dp_shard", int(plan["dp_shard"] or 0), dp_applied)
        stage_applied = int(zs.get("stage", 1)) if zs else 0
        if "zero_stage" in plan and \
                int(plan["zero_stage"] or 0) != stage_applied:
            _drift("zero_stage", int(plan["zero_stage"] or 0),
                   stage_applied)
        if zs is not None and plan.get("bucket_mb") and \
                zs.get("bucket_bytes") and \
                int(plan["bucket_mb"]) * 2 ** 20 != int(zs["bucket_bytes"]):
            _drift("bucket_mb", int(plan["bucket_mb"]),
                   int(zs["bucket_bytes"]) // 2 ** 20)
        gm = next((e for e in reversed(entries)
                   if e["pass"] == "gradient_merge"), None)
        gm_applied = int(gm.get("k", 0)) if gm else 1
        if "grad_merge" in plan and \
                int(plan["grad_merge"] or 1) != gm_applied:
            _drift("grad_merge", int(plan["grad_merge"] or 1), gm_applied)
        if "ring" in plan:
            has_ring = any(op.type == "ring_attention"
                           for b in program.blocks for op in b.ops)
            if bool(plan["ring"]) != has_ring:
                _drift("ring", bool(plan["ring"]), has_ring)
        if "tp_degree" in plan:
            # the applied tp degree is a BUILD property (a plan claiming
            # tp on a plain build, or a tp build whose plan says 0, is
            # the same knobs-never-ran drift as the ring knob); the
            # detection rule is shared with the planner's pinning
            from ..core.pass_framework import built_tp_degree
            tp_applied = built_tp_degree(program)
            if int(plan["tp_degree"] or 0) != tp_applied:
                _drift("tp_degree", int(plan["tp_degree"] or 0),
                       tp_applied)
        if "scan_hoist" in plan:
            # the hoist is a dispatch knob recorded by mark_scan_hoist —
            # a plan that priced the publish at 1/K over a program
            # nobody marked (or a marked program whose plan priced the
            # looped wire) attributes bytes that never moved
            hoist_applied = "scan_hoist" in order
            if bool(plan["scan_hoist"]) != hoist_applied:
                _drift("scan_hoist", bool(plan["scan_hoist"]),
                       hoist_applied)


# ---------------------------------------------------------------------------
# suite 3: donation / alias analyzer
# ---------------------------------------------------------------------------
def _donated_names(program: Program) -> Set[str]:
    """Persistables the jitted step donates (donate_argnums=(0,) over the
    whole state dict): all of them — with the ZeRO shards and elastic/gm
    accumulators called out by the sharper checks."""
    return {v.name for b in program.blocks for v in b.vars.values()
            if v.persistable}


def _check_donation(program: Program, startup: Optional[Program],
                    fetch_roots: Set[str], out: List[Diagnostic]):
    block = program.global_block()

    # V301: alias-creating assigns between persistables in the STARTUP
    # (eager) program.  `assign` binds the same device buffer under two
    # scope names; the next jitted step donates the state dict, so XLA
    # receives one buffer twice — an execution error at best, silent
    # reuse at worst.  (The Lookahead optimizer routes this through
    # scale(1.0) for exactly this reason.)
    for prog in ([startup] if startup is not None else []):
        sb = prog.global_block()
        for i, op in enumerate(sb.ops):
            if op.type != "assign":
                continue
            src = (op.inputs.get("X") or [None])[0]
            dst = (op.outputs.get("Out") or [None])[0]
            sv = _var_of(sb, src) if src else None
            dv = _var_of(sb, dst) if dst else None
            # the MAIN program's var table decides donation: startup
            # often declares mirrors of main persistables
            mv_src = _var_of(block, src) if src else None
            mv_dst = _var_of(block, dst) if dst else None
            src_p = (sv is not None and sv.persistable) or \
                (mv_src is not None and mv_src.persistable)
            dst_p = (dv is not None and dv.persistable) or \
                (mv_dst is not None and mv_dst.persistable)
            if src_p and dst_p and src != dst:
                out.append(Diagnostic(
                    "V301", ERROR,
                    f"startup assigns persistable {src!r} into "
                    f"persistable {dst!r}: both scope names alias ONE "
                    f"device buffer, which the jitted step then donates "
                    f"twice (use scale(x, 1.0) to copy instead)",
                    block_idx=0, op_idx=i, op_type=op.type,
                    op_uid=op.attrs.get("op_uid"), var=dst))

    # V302: read-after-donation.  The optimizer commit is the donation
    # point of a persistable's old buffer: once an Optimize-role op has
    # written param/slot P, a LATER forward/backward-role op reading P
    # sees the UPDATED value — gradients computed against half-updated
    # state, the classic swapped-pass-order bug.  (Optimize-role readers
    # are the masked-commit machinery reading its own temps: fine.)
    donated_at: Dict[str, Tuple[int, OpDesc]] = {}
    donated = _donated_names(program)
    for i, op in enumerate(block.ops):
        if _is_fwd_bwd_read(op) and op.type not in ("feed", "fetch"):
            for n in op.input_names():
                hit = donated_at.get(n)
                if hit is not None:
                    j, wop = hit
                    out.append(Diagnostic(
                        "V302", ERROR,
                        f"{op.type!r} (role fwd/bwd) reads persistable "
                        f"{n!r} AFTER its optimizer commit by "
                        f"{wop.type!r} at op {j}: the old buffer is "
                        f"donated — this read sees the post-update "
                        f"value (pass ordering bug)",
                        block_idx=0, op_idx=i, op_type=op.type,
                        op_uid=op.attrs.get("op_uid"), var=n))
        if _is_optimize_write(op):
            for n in op.output_names():
                if n in donated:
                    donated_at.setdefault(n, (i, op))

    # V303: fetching a per-rank shard.  dp_shard persistables live
    # sharded over the mesh (CompiledProgram feeds them P("dp")); a
    # fetch replicates/aggregates, returning one rank's slice (or a
    # meaningless pmean of disjoint shards) — and snapshotting it
    # through a fetch races the donation.  Checkpoints read the GLOBAL
    # persistable through the scope instead.
    if fetch_roots:
        for b in program.blocks:
            for v in b.vars.values():
                if v.attrs.get("dp_shard") and v.name in fetch_roots:
                    out.append(Diagnostic(
                        "V303", ERROR,
                        f"fetch of ZeRO-sharded slot {v.name!r}: each "
                        f"rank holds 1/{v.attrs['dp_shard']} of it — a "
                        f"fetch returns garbage; snapshot it via "
                        f"Executor.checkpoint_snapshot instead",
                        block_idx=b.idx, var=v.name))


# ---------------------------------------------------------------------------
# suite 4: retrace lint
# ---------------------------------------------------------------------------
def _check_retrace(program: Program, out: List[Diagnostic]):
    block = program.global_block()
    for v in block.vars.values():
        if not v.is_data:
            continue
        if v.shape is None or len(v.shape) == 0:
            out.append(Diagnostic(
                "V403", WARNING,
                f"feed {v.name!r} is declared rank-0: with any scalar "
                f"feed in the signature the batch-dim bucketing policy "
                f"disables itself and every ragged batch retraces "
                f"(declare it shape [1] and reshape instead)",
                block_idx=0, var=v.name))
            continue
        dyn_tail = [i for i, d in enumerate(v.shape) if int(d) == -1
                    and i > 0]
        if dyn_tail:
            out.append(Diagnostic(
                "V401", WARNING,
                f"feed {v.name!r} shape {list(v.shape)} is dynamic in "
                f"dim(s) {dyn_tail}: FLAGS_feed_bucketing pads only the "
                f"leading batch dim, so every distinct length in those "
                f"dims compiles a fresh executable (pad/bucket them "
                f"host-side — io/bucketing.py)",
                block_idx=0, var=v.name))
    for b in program.blocks:
        for i, op in enumerate(b.ops):
            for k, val in op.attrs.items():
                leaves = val if isinstance(val, (list, tuple)) else (val,)
                if any(isinstance(leaf, np.ndarray) or
                       type(leaf).__module__.startswith("jax")
                       for leaf in leaves):
                    out.append(Diagnostic(
                        "V402", WARNING,
                        f"op attr {k!r} holds a Python-captured array "
                        f"constant: it is baked "
                        f"into the trace and breaks fingerprint "
                        f"stability — a per-step value here retraces "
                        f"every step (feed it instead)",
                        block_idx=b.idx, op_idx=i, op_type=op.type,
                        op_uid=op.attrs.get("op_uid"), var=None))
                    break


# ---------------------------------------------------------------------------
# suite 5: sharding-propagation layout analyzer
# ---------------------------------------------------------------------------
def _check_layout(program: Program, out: List[Diagnostic]):
    """V601-V605 via the sharding-propagation analyzer
    (static/layout_analysis.py): infer every var's layout over the
    dp × mp mesh from the builders' annotations and flag kernel-contract
    conflicts, missing reductions, redundant reshards, mesh-axis
    disagreements and indivisible shards.  Model-axis findings only — a
    program with no tensor-parallel structure can't produce any."""
    from .layout_analysis import propagate_shardings
    out.extend(propagate_shardings(program).diagnostics)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def check_program(program: Program, level: str = "all",
                  startup: Optional[Program] = None,
                  fetch_list: Optional[Sequence] = None,
                  suppress: Iterable[str] = (),
                  raise_on_error: bool = False) -> VerifyReport:
    """Statically verify `program`'s op IR; returns a `VerifyReport`.

    ``level``: "graph" | "collective" | "donation" | "retrace" |
    "layout" | "all" (cumulative: "donation" runs
    graph+collective+donation), or an int 1-5.  ``startup``
    additionally checks init-time alias hazards (V301).  ``fetch_list``
    (vars or names) sharpens the dangling-var and shard-fetch checks.
    ``suppress`` drops diagnostic codes an allowlist has accepted.
    ``raise_on_error=True`` raises `ProgramVerificationError` when any
    error-severity diagnostic remains.

    Wired as ``paddle.static.check_program``; the same walk is run
    automatically at first compile and after every rewrite pass when
    ``PADDLE_TPU_VERIFY`` is set (docs/static_analysis.md).
    """
    if isinstance(level, int):
        depth = max(1, min(5, level))
    else:
        try:
            depth = _LEVELS[str(level)]
        except KeyError:
            raise ValueError(
                f"unknown verify level {level!r}: expected one of "
                f"{sorted(_LEVELS)} or an int 1-5")
    fetch_roots: Set[str] = set()
    for f in (fetch_list or []):
        fetch_roots.add(f.name if hasattr(f, "name") else str(f))
    fetch_roots.update(getattr(program, "_fetch_names", ()) or ())

    diags: List[Diagnostic] = []
    _check_graph(program, fetch_roots, diags)
    if depth >= 2:
        _check_collectives(program, diags)
    if depth >= 3:
        _check_donation(program, startup, fetch_roots, diags)
    if depth >= 4:
        _check_retrace(program, diags)
    if depth >= 5:
        _check_layout(program, diags)

    suppress = set(suppress)
    if suppress:
        diags = [d for d in diags if d.code not in suppress]
    from ..core.pass_framework import applied_passes
    report = VerifyReport(diags, level=str(level),
                          applied_passes=applied_passes(program))
    if raise_on_error:
        report.raise_on_error()
    return report


def verify_mode() -> str:
    """The PADDLE_TPU_VERIFY env contract: "" (off), "warn" (report
    defects as RuntimeWarnings), "strict" (raise on error diagnostics).
    Any other truthy value (e.g. "1") means "warn"."""
    raw = os.environ.get(VERIFY_ENV, "").strip().lower()
    if raw in ("", "0", "off", "false", "no"):
        return ""
    if raw == "strict":
        return "strict"
    return "warn"


def self_check(program: Program, pass_name: str,
               startup: Optional[Program] = None):
    """Post-rewrite self-verification hook for the rewrite passes
    (sharding, elastic, gradient_merge, recompute, AMP): a no-op unless
    PADDLE_TPU_VERIFY is set; in "strict" mode a pass that emitted
    broken IR raises at the rewrite site (with the pass named), in
    "warn" mode it warns and continues."""
    mode = verify_mode()
    if not mode:
        return None
    report = check_program(program, level="all", startup=startup)
    if report.errors and mode == "strict":
        raise ProgramVerificationError(report,
                                       context=f"after pass {pass_name!r}")
    if report.diagnostics:
        import warnings
        warnings.warn(
            f"PADDLE_TPU_VERIFY: pass {pass_name!r} left "
            f"{len(report.errors)} error(s) / {len(report.warnings)} "
            f"warning(s):\n{report.render()}", RuntimeWarning,
            stacklevel=3)
    return report


_verified_fingerprints: Set[Tuple] = set()


def verify_first_compile(program: Program,
                         fetch_list: Optional[Sequence] = None):
    """First-compile hook (Executor/_run_compiled, run_steps, and
    CompiledProgram on a trace-cache miss): verifies each distinct
    (program, fetch set) once per process when PADDLE_TPU_VERIFY is
    set.  Memoized by fingerprint + fetch names — the fetch set is part
    of what gets checked (V107 missing fetch, V303 shard fetch), so a
    later compile of the same program with new fetches re-verifies.
    The check costs an IR walk + abstract evaluation, so it rides the
    (already slow) compile path only."""
    mode = verify_mode()
    if not mode:
        return None
    fetch_key = tuple(sorted(
        f.name if hasattr(f, "name") else str(f)
        for f in (fetch_list or [])))
    try:
        fp = (program.fingerprint(), fetch_key)
    except Exception:
        fp = None
    if fp is not None and fp in _verified_fingerprints:
        return None
    report = check_program(program, level="all", fetch_list=fetch_list)
    if report.errors and mode == "strict":
        # memoize only CLEAN outcomes: a retried run of the same broken
        # program must hit the gate again, not the memo
        raise ProgramVerificationError(report, context="first compile")
    if fp is not None:
        _verified_fingerprints.add(fp)
    if report.diagnostics:
        import warnings
        warnings.warn(
            f"PADDLE_TPU_VERIFY (first compile): {len(report.errors)} "
            f"error(s) / {len(report.warnings)} warning(s):\n"
            f"{report.render()}", RuntimeWarning, stacklevel=3)
    return report
