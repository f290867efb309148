"""Block-paged KV-cache pool with copy-on-write prefix sharing.

The fixed-slot generation engine gave every decode slot a dense
max-length KV buffer: HBM paid the worst case for every sequence, and
two users sharing a system prompt paid for it twice.  This pool is the
established fix (vLLM's PagedAttention block manager; SGLang's prefix
cache): KV lives in FIXED-SIZE PAGES of ``page_tokens`` token columns,
allocated ONCE at engine start as a single ``[L, P, H, T, Dh]`` slab
per tensor (one page id indexes every layer's slice — the standard
one-table-for-all-layers trick), and each sequence owns a PAGE TABLE
mapping logical token positions to page ids.

Sharing: at prefill every prompt page (each full page and the final
partial page) is registered under the hash of the EXACT token prefix it
completes — KV column ``t`` depends only on tokens ``<= t`` (causal,
deterministic eval), so two prompts with the same head produce bitwise-
identical page content and the later one just bumps a refcount instead
of recomputing/storing it.  Writes go through copy-on-write: appending
a decode column into a page whose refcount > 1 first copies the page,
so sharers never observe each other's continuations.

Admission is by PAGE RESERVATION, not slot count: a sequence reserves
its worst case (``pages_for_request`` — ``ceil((prompt + max_new) /
page_tokens)``, plus one COW allowance when the prompt's final page is
partial and may be shared out from under it) before it is admitted, and
every later allocation (fresh page or COW copy) is charged against that
reservation — ``reserve()`` can refuse, but a reserved sequence can
never hit an empty free list mid-decode.  Actual
usage is bounded by the reservation (sharing and early EOS only
reduce), so the pool trades no correctness for the oversubscription the
fixed-slot engine could never attempt.

Retention (the radix prefix cache's storage contract): pages normally
free when their last sharer retires, but ``serving/prefix_cache.py``
may PIN a page past that point so a hot system prompt stays resident
across non-concurrent requests.  Pinned pages whose only reference is
the pin are a fourth accounting class — RETAINED — beside
free/live/reserved: they are counted as reclaimable headroom by
``pages_available`` (admission never starves because of retention),
and an allocation that finds the free list empty asks the registered
reclaimer (``set_reclaimer``) to evict retained pages before it may
raise.  ``truncate`` is the speculative decoder's rollback: drop the
page-table tail past a committed length and refund the charge.

Sizing belongs to the planner: build the pool from
``static.plan_program``'s sibling ``static.page_budget(model)`` (the
HBM-walker sizing path) via ``PagedKVPool.from_plan``; the plan is
recorded on the pool and ``budget_drift`` re-derives it so hand-edited
pool geometry is detectable, V504-style.

int8 pages (``kv_dtype="int8"``): the slabs store K/V as int8 with a
per-(layer, page, head) fp32 DEQUANT SCALE in a sidecar array
(``x ≈ q * scale``, scale = absmax/127).  Quantization happens on
write and dequantization inside ``gather``, so everything above the
slab — page tables, COW sharing, radix ``adopt_prefix``, speculative
``truncate`` — rides unchanged as page-id plumbing.  The write policy
is REQUANTIZE-ON-GROW: a column whose absmax exceeds the page's
current scale requantizes the resident columns under the grown scale
(ratio ≤ 1, magnitudes only shrink) before the new column lands, so a
page's columns always share one scale and saturation is structurally
impossible; ``quant_scale_clips`` counts any defensive clamp anyway.
``page_bytes`` prices the int8 itemsize plus the scale sidecar, which
is what lets ``static.page_budget(kv_dtype="int8")`` carve ~2× the
pages at equal HBM.

Geometry comes from the model's CACHE DESCRIPTION (``cache_spec_of``):
a list of layer groups, each ``kv`` (layers, kv heads, head dim — a
column a token, in pages) or ``state`` (layers, the arrays one sequence
holds whatever its length — a recurrent layer's state).  A model with a
``state`` group gets a SECOND KIND OF CACHE IN THE SAME MANAGER:
``StateSlots``, device arrays ``[layers, slots, ...]`` allocated once.
``reserve`` hands out a state slot with the page reservation (on
``table.state_slot``) and ``close_sequence`` gives both back, so every
retire / cancel / failure path that returns pages returns the state.
The recurrent state never crosses the host link.

KV ON THE DEVICE ONLY, A GROUP PER KIND OF RETENTION.  A description whose
``kv`` groups (one or several) each say what they retain — ``"retain":
"all"`` (every column, up to the context served) or a window in columns
(the last W tokens, a RING written at ``position mod W``) — keeps its KV
in the slots too: per group two arrays ``[layers, slots, kv heads,
columns, head dim]`` (``device_kv_arrays``) ride the ``StateSlots``
before the recurrent state, the compiled steps are given them whole and
write the new column in place, and no KV byte crosses the host link.
Every model on the engine's compiled route is described so
(``serving/step_program.py``).  The pool then allocates NO host slabs
(``device_only``): its page tables do admission and accounting — a
sequence's pages count the tokens its ``"all"`` group holds
(``account_prompt`` / ``account_column``), a window's ring is a constant
of the slot, priced with the state — and nothing is registered for prefix
sharing (a ring cannot be shared or truncated by page).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import metrics
from ..core.compile_cache import next_pow2 as _next_pow2
from ..profiler import NO_PHASE, Phase, RecordEvent

__all__ = ["PagedKVPool", "PageTable", "PagePoolExhaustedError",
           "StateSlots", "budget_drift", "cache_spec_of", "device_kv_arrays",
           "kv_geometry", "retained_kv_groups", "state_groups",
           "state_slot_bytes"]


# -- the model's cache description -------------------------------------------
def cache_spec_of(config) -> List[Dict]:
    """The cache description of a decoder config: what it states itself
    (``config.cache_spec()``, or the ``"cache"`` key of a plain dict), else
    one ``kv`` group of ``num_layers`` x ``num_heads`` full-attention
    layers (every GPTConfig-shaped object)."""
    if hasattr(config, "cache_spec"):
        return config.cache_spec()
    get = config.get if isinstance(config, dict) else \
        lambda k, d=None: getattr(config, k, d)
    if get("cache"):
        return list(get("cache"))
    heads = int(get("num_heads"))
    return [{"kind": "kv", "layers": int(get("num_layers")),
             "kv_heads": heads,
             "head_dim": int(get("hidden_size")) // heads}]


def retained_kv_groups(spec) -> List[Dict]:
    """The ``kv`` groups that state what they retain (``"all"`` or a
    window in columns): all of the description's ``kv`` groups, or none —
    a description with them keeps its KV on the device only, one without
    in the host pages (the eager route)."""
    kv = [g for g in spec if g["kind"] == "kv"]
    said = [g for g in kv if g.get("retain") is not None]
    if said and len(said) != len(kv):
        raise ValueError(
            "a cache description states `retain` on every kv group or on "
            "none (device-only KV and host pages do not mix)")
    for g in said:
        if g["retain"] != "all" and not (
                isinstance(g["retain"], int) and g["retain"] > 0):
            raise ValueError(f"retain is 'all' or a window in columns, "
                             f"got {g['retain']!r}")
    return said


def kv_geometry(spec) -> tuple:
    """(layers, kv heads, head dim) of the ``kv`` group whose tokens the
    pool's pages count: the description's one ``kv`` group, or, where the
    groups state their retention (``retained_kv_groups``), the one that
    retains ``"all"`` (else the first: every group a window).  Several
    groups without ``retain`` — a latent cache beside a plain one on the
    host-page path — are ROADMAP D9's remainder."""
    kv = [g for g in spec if g["kind"] == "kv"]
    said = retained_kv_groups(spec)
    if said:
        kv = [g for g in said if g["retain"] == "all"][:1] or said[:1]
    if len(kv) != 1:
        raise NotImplementedError(
            f"the page pool holds exactly one kv layer group without "
            f"`retain`, the cache description has {len(kv)}")
    return int(kv[0]["layers"]), int(kv[0]["kv_heads"]), \
        int(kv[0]["head_dim"])


def device_kv_arrays(spec, max_context: int) -> List[Dict]:
    """The arrays a description with ``retain`` keeps on the device, a K
    and a V per group in the description's order — {"name" (``k<g>`` /
    ``v<g>``), "layers", "dtype", "shape" [kv heads, columns, head dim] of
    one slot's entry a layer, "window" (0: every column)}: a window's
    columns, or the power of two over `max_context`."""
    out = []
    for g, group in enumerate(retained_kv_groups(spec)):
        window = 0 if group["retain"] == "all" else int(group["retain"])
        columns = window or _next_pow2(int(max_context))
        for name in ("k", "v"):
            out.append({"name": f"{name}{g}", "layers": int(group["layers"]),
                        "dtype": group.get("dtype", "float32"),
                        "shape": [int(group["kv_heads"]), columns,
                                  int(group["head_dim"])],
                        "window": window})
    return out


def state_groups(spec) -> List[Dict]:
    return [g for g in spec if g["kind"] == "state"]


def state_slot_bytes(spec, max_context: int = 0) -> int:
    """Bytes one sequence holds on the device whatever its length: its
    recurrent state, all groups, and — for a description with ``retain``,
    given the context served — its slot of the device-only KV arrays."""
    from ..core.dtype import np_dtype
    kv = device_kv_arrays(spec, max_context) \
        if retained_kv_groups(spec) else []
    return sum(int(g["layers"]) * int(np.prod(a["shape"]))
               * np_dtype(a["dtype"]).itemsize
               for g in state_groups(spec) for a in g["arrays"]) \
        + sum(a["layers"] * int(np.prod(a["shape"]))
              * np_dtype(a["dtype"]).itemsize for a in kv)


class PagePoolExhaustedError(RuntimeError):
    """A page allocation found the free list empty.  Reservation
    accounting makes this unreachable from the engine — raising it
    loudly means the accounting itself is broken, not the load."""


class PageTable:
    """One sequence's mapping from logical token positions to pages.

    ``pages[j]`` holds positions ``[j*T, (j+1)*T)``; ``length`` tokens
    are valid.  ``reserved`` is the worst-case page count admission
    granted; ``charged`` counts the allocations (fresh + COW) already
    consumed from it.  ``state_slot`` is the sequence's slot in the
    pool's ``StateSlots`` (None for a model without recurrent state)."""

    __slots__ = ("pages", "length", "reserved", "charged", "state_slot")

    def __init__(self, reserved: int, state_slot: Optional[int] = None):
        self.pages: List[int] = []
        self.length = 0
        self.reserved = int(reserved)
        self.charged = 0
        self.state_slot = state_slot


class StateSlots:
    """Per-sequence state of fixed size, on the device.

    One array per entry of each ``state`` group of the cache description,
    ``[layers, slots, *shape]``, allocated ONCE here; a sequence owns slot
    ``i`` (row ``i`` of every array) from ``reserve`` to ``release``.

    ONE COPY OF THE STATE EXISTS, and whoever writes it owns its buffers
    while it does.  The decode step takes ``arrays`` whole and DONATES them
    through its compiled program (``StepPrograms.decode``): XLA updates
    each layer's entry where it lies, and when the step returns the arrays
    that went in are dead (``is_deleted()``).  ``rebind`` is therefore not
    optional: it must take the step's results before anything reads the
    state again, so ``row`` and ``install`` only ever see rebound arrays
    (``serving.gen.state_in_place`` counts the steps whose old arrays were
    all dead at ``rebind``, ``serving.gen.state_copied`` those where a
    backend ignored the donation and copied).  A step that raises after it
    has donated leaves nothing to rebind: ``recover`` puts zeroed arrays
    in place of dead ones (every sequence is failed then anyway).
    ``install`` writes one slot from a prefill's result in place, by the
    same hand-over (the old array is donated).  A prefill starts from zero state
    and its result overwrites the whole slot, so a reused slot never
    shows its last owner's state (``serving.gen.state_resets`` counts
    those overwrites).

    ``device_kv`` (``device_kv_arrays``) puts a description's device-only
    KV arrays FIRST among ``arrays``, before the state's, in the order the
    step contract takes them: they are the cache itself — donated through
    the decode step, which writes each row's new column where the array
    lies, and written by ``install`` from a prefill's result (a window
    group's ring whole; a group that keeps every column up to the prompt's
    bucket: columns past it keep what the slot's last owner left, which no
    row reads — a row sees its own ``length`` columns, each written by its
    own prompt or step first).  Mutated on the engine's decode thread
    only."""

    def __init__(self, groups: Sequence[Dict], slots: int,
                 device_kv: Sequence[Dict] = ()):
        import jax
        import jax.numpy as jnp
        from ..core.dtype import np_dtype
        self.groups = [dict(g) for g in groups]
        self.device_kv = [dict(a) for a in device_kv]
        self.slots = int(slots)
        if self.slots < 1 or not (self.groups or self.device_kv):
            raise ValueError("StateSlots needs >= 1 slot and a state group "
                             "or device-only KV arrays")
        self.arrays: Dict[str, "jax.Array"] = {}
        # once an engine: the device arrays, allocated here and never again
        with Phase("kv_pool/allocate", slots=self.slots, pages=0) as phase:
            for a in self.device_kv:
                self.arrays[a["name"]] = jnp.zeros(
                    (a["layers"], self.slots) + tuple(a["shape"]),
                    np_dtype(a["dtype"]))
            self.kv_slot_bytes = sum(
                v.nbytes for v in self.arrays.values()) // self.slots
            for g in self.groups:
                for a in g["arrays"]:
                    if a["name"] in self.arrays:
                        raise ValueError(
                            f"two state arrays named {a['name']!r}")
                    self.arrays[a["name"]] = jnp.zeros(
                        (int(g["layers"]), self.slots) + tuple(a["shape"]),
                        np_dtype(a["dtype"]))
            self.slot_bytes = sum(
                v.nbytes for v in self.arrays.values()) // self.slots
            phase.set(bytes=self.slot_bytes * self.slots)
        self._free: List[int] = list(range(self.slots - 1, -1, -1))
        self._written = set()       # slots that have held a sequence
        self._write_shapes = set()  # `install`s whose writes are compiled
        # one slot's entry (a prefill's result, shorter along the context
        # where it is a prompt's KV) into its row, in place
        self._write = jax.jit(
            lambda slab, new, slot: jax.lax.dynamic_update_slice(
                slab, new.astype(slab.dtype),
                (slot * 0, slot) + (slot * 0,) * (slab.ndim - 2)),
            donate_argnums=0)
        self._publish()

    @property
    def names(self) -> List[str]:
        return list(self.arrays)

    def built_for(self, spec) -> bool:
        """Whether these slots hold what the cache description `spec`
        states: its state groups, and a K, V pair for each of its kv
        groups' layers and retention (their columns follow the context
        served)."""
        want = [(int(g["layers"]),
                 0 if g["retain"] == "all" else int(g["retain"]))
                for g in retained_kv_groups(spec)]
        return self.groups == state_groups(spec) and want == [
            (a["layers"], a["window"]) for a in self.device_kv[::2]]

    @property
    def used(self) -> int:
        return self.slots - len(self._free)

    @property
    def nbytes(self) -> int:
        return self.slot_bytes * self.slots

    def can_reserve(self) -> bool:
        return bool(self._free)

    def reserve(self) -> int:
        if not self._free:
            raise PagePoolExhaustedError(
                f"all {self.slots} state slots are taken")
        slot = self._free.pop()
        self._publish()
        return slot

    def release(self, slot: int):
        if slot in self._free or not 0 <= slot < self.slots:
            raise ValueError(f"state slot {slot} is not held")
        self._free.append(int(slot))
        self._publish()

    def install(self, slot: int, **new):
        """Write one sequence's cache — arrays ``[layers, 1, *shape]`` by
        name, as a prefill returns them (a KV group that keeps every
        column: the prompt's bucket of them) — into `slot`, on the
        device."""
        if sorted(new) != sorted(self.arrays):
            raise ValueError(
                f"install needs {sorted(self.arrays)}, got {sorted(new)}")
        shapes = tuple(v.shape for v in new.values())
        obtained = NO_PHASE
        if shapes not in self._write_shapes:
            # a program obtained: `_write`'s executables for a prefill's
            # results of these shapes (one set a prompt bucket)
            self._write_shapes.add(shapes)
            obtained = Phase("jit/program", fn="StateSlots._write",
                             kind="install", rows=1, bucket=(
                                 new[self.device_kv[0]["name"]].shape[3]
                                 if self.device_kv else 0))
        with obtained:
            for name, value in new.items():
                self.arrays[name] = self._write(self.arrays[name], value,
                                                np.int32(slot))
        if slot in self._written:
            metrics.count("gen.state_resets")
        self._written.add(slot)

    def rebind(self, **new):
        """Take a decode step's updated arrays in place of the old, which
        the step was given to write into: all of them, after every step."""
        if sorted(new) != sorted(self.arrays):
            raise ValueError(f"rebind needs {sorted(self.arrays)}, got "
                             f"{sorted(new)}")
        in_place = True
        for name, value in new.items():
            old = self.arrays[name]
            if value.shape != old.shape or value.dtype != old.dtype:
                raise ValueError(
                    f"state array {name!r}: step returned {value.dtype}"
                    f"{value.shape}, the slab is {old.dtype}{old.shape}")
            in_place &= old.is_deleted()
            self.arrays[name] = value
        metrics.count("gen.state_in_place" if in_place
                      else "gen.state_copied")

    def recover(self):
        """After a failed step or install: zeroed arrays in place of the
        ones a donation left dead."""
        import jax.numpy as jnp
        for name, a in self.arrays.items():
            if a.is_deleted():
                self.arrays[name] = jnp.zeros(a.shape, a.dtype)

    def row(self, slot: int) -> Dict[str, np.ndarray]:
        """Host copies of one slot's state (tests and debugging: this is
        the one place the state crosses the host link)."""
        return {n: np.asarray(a[:, slot]) for n, a in self.arrays.items()}

    def _publish(self):
        metrics.gauge("state.slots_total", self.slots)
        metrics.gauge("state.slots_used", self.used)
        metrics.gauge("state.bytes", self.nbytes)
        if self.device_kv:
            metrics.gauge("kv.device_bytes", self.kv_slot_bytes * self.slots)


class PagedKVPool:
    """Fixed-size paged KV storage shared by every active sequence.

        pool = PagedKVPool(num_layers=4, num_heads=4, head_dim=64,
                           page_tokens=16, num_pages=256)
        table = pool.open_sequence(prompt, k_lhpd, v_lhpd, reserved=R)
        k, v = pool.gather(table)          # [L, H, len, Dh] dense views
        pool.append_column(table, k_col, v_col)
        pool.close_sequence(table)         # refcounts drop, pages free

    All mutation happens on the engine's single decode thread; the
    internal lock only protects the stats surface other threads read.
    """

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 page_tokens: int = 16, num_pages: int = 64,
                 dtype=np.float32, plan: Optional[Dict] = None,
                 kv_dtype=None, state: Optional[StateSlots] = None):
        if page_tokens < 1 or num_pages < 1:
            raise ValueError(
                f"need positive page_tokens/num_pages, got "
                f"{page_tokens}/{num_pages}")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.page_tokens = int(page_tokens)
        self.num_pages = int(num_pages)
        # kv_dtype is the planner-facing name for the same knob
        self.dtype = np.dtype(kv_dtype if kv_dtype is not None else dtype)
        self.is_quantized = self.dtype == np.int8
        # ONE slab per tensor, allocated up front: page id p is
        # self.k[:, p] across every layer (no per-sequence allocation
        # ever happens again)
        # the second kind of cache: recurrent state slots (None for a
        # model whose cache description has no `state` group); where they
        # hold the KV itself (`device_only`: a cache description with
        # `retain`) the tables account and nothing is stored here
        self.state = state
        shape = (self.num_layers, self.num_pages, self.num_heads,
                 self.page_tokens, self.head_dim)
        self.k = self.v = None
        if not self.device_only:
            with Phase("kv_pool/allocate", slots=0, pages=self.num_pages,
                       bytes=2 * int(np.prod(shape)) * self.dtype.itemsize):
                self.k = np.zeros(shape, self.dtype)
                self.v = np.zeros(shape, self.dtype)
        if self.is_quantized:
            # per-(layer, page, head) fp32 dequant scale: x ≈ q * scale
            sshape = (self.num_layers, self.num_pages, self.num_heads)
            self.k_scale = np.zeros(sshape, np.float32)
            self.v_scale = np.zeros(sshape, np.float32)
        self.quant_scale_clips = 0
        self._refcount = np.zeros(self.num_pages, np.int32)
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._reserved_unallocated = 0
        # maintained on refcount 1<->2 transitions: _publish runs once
        # per appended token, so pages_shared must not scan the pool
        self._shared_pages = 0
        # prefix sharing: exact-token-prefix key -> page id, and back
        self._prefix: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        # retention: page ids the radix prefix cache holds one ref on;
        # a pinned page with refcount 1 is RETAINED (cache-only) and
        # reclaimable through _reclaim_cb.  RLock: the reclaimer runs
        # inside _alloc and calls back into unpin_page.
        self._radix_pinned: set = set()
        self._reclaim_cb = None
        self._mu = threading.RLock()
        self.cow_copies = 0
        self.prefix_hits = 0
        self.plan = dict(plan) if plan else None
        self._publish()

    @classmethod
    def from_plan(cls, plan: Dict, dtype=np.float32) -> "PagedKVPool":
        """Build a pool from a ``static.page_budget`` plan dict (records
        the plan so `budget_drift` can re-derive and compare it).  A plan
        whose cache description has a ``state`` group gets its
        ``StateSlots`` — ``max_slots`` of them — allocated here."""
        spec = plan.get("cache") or []
        groups = state_groups(spec)
        # the KV itself, on the device only, where the groups say `retain`
        device_kv = device_kv_arrays(spec, int(plan["max_context"])) \
            if retained_kv_groups(spec) else []
        return cls(num_layers=int(plan["num_layers"]),
                   num_heads=int(plan["num_heads"]),
                   head_dim=int(plan["head_dim"]),
                   page_tokens=int(plan["page_tokens"]),
                   num_pages=int(plan["pages"]),
                   dtype=plan.get("kv_dtype", dtype), plan=plan,
                   state=StateSlots(groups, int(plan["max_slots"]),
                                    device_kv)
                   if groups or device_kv else None)

    # -- geometry -----------------------------------------------------------
    @property
    def device_only(self) -> bool:
        """Whether the KV is the state slots' device arrays and no host
        slab exists: what the state slots were built with says it."""
        return self.state is not None and bool(self.state.device_kv)

    @property
    def page_bytes(self) -> int:
        """Bytes one page occupies across both tensors and all layers —
        for int8 pages that is the int8 data plus the per-(layer, head)
        fp32 scale sidecar rows for both K and V."""
        data = 2 * self.num_layers * self.num_heads * self.page_tokens \
            * self.head_dim * self.dtype.itemsize
        if self.is_quantized:
            data += 2 * self.num_layers * self.num_heads * 4
        return data

    @property
    def tp_degree(self) -> int:
        """The tensor-parallel degree the recorded plan sized this pool
        for (1 = single-chip).  The host slab always holds the full
        head dim — page ids, refcounts, and tables are GLOBAL token
        geometry — but on a tp mesh each chip's resident shard of a page
        is ``[L, H/tp, T, Dh]``, so the per-chip byte charge divides."""
        return int((self.plan or {}).get("tp_degree", 1))

    @property
    def page_bytes_per_chip(self) -> int:
        """Bytes of one page actually resident per chip: `page_bytes`
        over the head-sharding tp degree (the number `page_budget`
        carved pages against)."""
        return self.page_bytes // max(1, self.tp_degree)

    def pages_needed(self, n_tokens: int) -> int:
        """Worst-case pages a sequence of ``n_tokens`` total (prompt +
        generated) occupies — the admission reservation unit."""
        return -(-max(0, int(n_tokens)) // self.page_tokens)

    def pages_for_request(self, prompt_tokens: int,
                          new_tokens: int) -> int:
        """Admission reservation for one request: the worst-case page
        count plus one COW allowance when the prompt's final page is
        partial.  That page is prefix-registered, so a later identical
        prompt may share it — and then THIS sequence's first decode
        write needs a copy on top of its worst case.  (Full prompt
        pages are never decode-written and COW copies are never
        re-registered, so one page covers every possible copy.)"""
        p = max(0, int(prompt_tokens))
        extra = 1 if p % self.page_tokens else 0
        return self.pages_needed(p + max(0, int(new_tokens))) + extra

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_reserved(self) -> int:
        """Reserved-but-not-yet-allocated pages (admission headroom
        already promised to running sequences)."""
        return self._reserved_unallocated

    @property
    def pages_shared(self) -> int:
        return self._shared_pages

    @property
    def pages_retained(self) -> int:
        """Pages held ONLY by the radix prefix cache (pinned, no live
        sequence) — resident-but-reclaimable, the fourth accounting
        class beside free/live/reserved."""
        with self._mu:
            return sum(1 for pid in self._radix_pinned
                       if self._refcount[pid] == 1)

    @property
    def pages_available(self) -> int:
        """Pages a NEW reservation may claim right now.  Retained pages
        count: the reclaimer evicts them on demand, so retention can
        never starve admission."""
        return len(self._free) + self.pages_retained \
            - self._reserved_unallocated

    def set_reclaimer(self, fn):
        """Register the radix cache's eviction hook: ``fn(n)`` must try
        to bring ``pages_free`` up to ``n`` by unpinning retained pages
        (called by ``_alloc`` before it may raise)."""
        self._reclaim_cb = fn

    # -- admission reservation ---------------------------------------------
    def can_reserve(self, n_pages: int) -> bool:
        return int(n_pages) <= self.pages_available and (
            self.state is None or self.state.can_reserve())

    def reserve(self, n_pages: int) -> PageTable:
        """Claim worst-case headroom for one sequence — and, where the
        model has recurrent state, its state slot; the returned table is
        the charge account every later allocation debits."""
        n = int(n_pages)
        if n > self.pages_available:
            raise PagePoolExhaustedError(
                f"cannot reserve {n} pages "
                f"({self.pages_available} available of {self.num_pages})")
        slot = self.state.reserve() if self.state is not None else None
        self._reserved_unallocated += n
        self._publish()
        return PageTable(n, state_slot=slot)

    def release(self, table: PageTable):
        """Return a table's unconsumed reservation (retire path, and the
        bail-out for sequences that reserved but never opened)."""
        left = table.reserved - table.charged
        if left > 0:
            self._reserved_unallocated -= left
        table.reserved = table.charged
        if table.state_slot is not None:
            with RecordEvent("engine/state_release", slot=table.state_slot):
                self.state.release(table.state_slot)
            table.state_slot = None
        self._publish()

    # -- page plumbing ------------------------------------------------------
    def _alloc(self, table: PageTable) -> int:
        if table.charged >= table.reserved:
            raise PagePoolExhaustedError(
                f"sequence exceeded its reservation "
                f"({table.reserved} pages)")
        if not self._free and self._reclaim_cb is not None:
            # retention consumed the free list: reservations were
            # granted counting retained pages as reclaimable, so the
            # radix cache must now make good on that promise
            self._reclaim_cb(1)
        if not self._free:
            raise PagePoolExhaustedError(
                "free list empty under outstanding reservations — "
                "reservation accounting bug")
        pid = self._free.pop()
        self._refcount[pid] = 1
        table.charged += 1
        self._reserved_unallocated -= 1
        return pid

    def _incref(self, pid: int):
        self._refcount[pid] += 1
        if self._refcount[pid] == 2:
            self._shared_pages += 1

    def _decref(self, pid: int):
        self._refcount[pid] -= 1
        if self._refcount[pid] == 1:
            self._shared_pages -= 1
        if self._refcount[pid] == 0:
            key = self._page_key.pop(pid, None)
            if key is not None and self._prefix.get(key) == pid:
                del self._prefix[key]
            self._free.append(pid)

    # -- retention (radix prefix cache hooks) -------------------------------
    def pin_page(self, pid: int):
        """Hold one reference on a page past last-sharer retirement (the
        radix cache's retention primitive).  Idempotent per page: a page
        carries at most one pin."""
        with self._mu:
            if self._refcount[pid] < 1:
                raise ValueError(f"cannot pin free page {pid}")
            if pid in self._radix_pinned:
                return
            self._radix_pinned.add(pid)
            self._incref(pid)
        self._publish()

    def unpin_page(self, pid: int):
        """Drop a pin (eviction path): the page frees now if no live
        sequence still references it."""
        with self._mu:
            if pid not in self._radix_pinned:
                return
            self._radix_pinned.discard(pid)
            self._decref(pid)
        self._publish()

    def adopt_prefix(self, table: PageTable, pids: Sequence[int],
                     n_tokens: int):
        """Map already-resident prefix pages into a fresh sequence's
        page table (the radix-hit fast path: refcount bumps, no writes,
        no charge against the reservation).  ``n_tokens`` must be the
        page-aligned token count the pages cover."""
        n = int(n_tokens)
        if n % self.page_tokens or len(pids) != n // self.page_tokens:
            raise ValueError(
                f"adopt_prefix needs page-aligned tokens: {n} tokens "
                f"vs {len(pids)} pages of {self.page_tokens}")
        if table.pages or table.length:
            raise ValueError("adopt_prefix needs a fresh page table")
        with self._mu:
            for pid in pids:
                if self._refcount[pid] < 1:
                    raise ValueError(
                        f"page {pid} is free — stale radix hit")
            for pid in pids:
                self._incref(pid)
                table.pages.append(int(pid))
            table.length = n
        self._publish()

    def truncate(self, table: PageTable, new_length: int):
        """Roll a sequence back to ``new_length`` committed tokens (the
        speculative decoder's rejection path): pages wholly past the
        boundary are dropped, and pages this table owned exclusively are
        refunded to its reservation so later decode can re-allocate
        them."""
        n = int(new_length)
        if n < 0 or n > table.length:
            raise ValueError(
                f"truncate to {n} outside [0, {table.length}]")
        keep = -(-n // self.page_tokens)
        with self._mu:
            dropped = table.pages[keep:]
            del table.pages[keep:]
            for pid in dropped:
                if self._refcount[pid] == 1 \
                        and pid not in self._radix_pinned:
                    # exclusively ours: the reservation gets the page
                    # back (shared/pinned drops keep their charge —
                    # conservative, never under-reserved)
                    table.charged -= 1
                    self._reserved_unallocated += 1
                self._decref(pid)
            table.length = n
        self._publish()

    # -- int8 page quantization ---------------------------------------------
    def _quantize_into(self, slab, scale_arr, pid: int, col_slice,
                       x: np.ndarray, s: np.ndarray):
        """Quantize fp ``x`` [L, H, n, Dh] under per-(L, H) scale ``s``
        and store into page ``pid`` at ``col_slice``.  The scale always
        covers the chunk's absmax (fresh-write or requantize-on-grow
        policy), so the clamp is defensive; any element it actually
        saturates is counted in ``quant_scale_clips``."""
        q = np.rint(np.divide(
            np.asarray(x, np.float32), s[:, :, None, None],
            out=np.zeros(x.shape, np.float32),
            where=s[:, :, None, None] > 0))
        clips = int(np.count_nonzero(np.abs(q) > 127))
        if clips:
            self.quant_scale_clips += clips
            metrics.count("kv.quant_scale_clips", clips)
            np.clip(q, -127, 127, out=q)
        slab[:, pid, :, col_slice] = q.astype(np.int8)

    def _store_page_chunk(self, pid: int, ncols: int,
                          k_chunk: np.ndarray, v_chunk: np.ndarray):
        """Install columns [0, ncols) of a FRESHLY allocated page (the
        prefill write).  fp pools store verbatim; int8 pools derive the
        page scale from the chunk's per-(layer, head) absmax."""
        if not self.is_quantized:
            self.k[:, pid, :, :ncols] = k_chunk
            self.v[:, pid, :, :ncols] = v_chunk
            return
        for slab, scale_arr, x in ((self.k, self.k_scale, k_chunk),
                                   (self.v, self.v_scale, v_chunk)):
            x = np.asarray(x, np.float32)
            s = np.max(np.abs(x), axis=(2, 3)) / 127.0
            scale_arr[:, pid] = s
            self._quantize_into(slab, scale_arr, pid, slice(0, ncols),
                                x, s)

    def _store_column(self, pid: int, off: int, k_col: np.ndarray,
                      v_col: np.ndarray):
        """Write one decode column at ``off`` into an EXCLUSIVE page.
        int8 pools requantize-on-grow: if the column's absmax exceeds
        the page's current scale, the resident columns are requantized
        under the grown scale first (ratio old/new ≤ 1 — magnitudes
        only shrink, so the rewrite itself can never clip)."""
        if not self.is_quantized:
            self.k[:, pid, :, off] = k_col
            self.v[:, pid, :, off] = v_col
            return
        for slab, scale_arr, col in ((self.k, self.k_scale, k_col),
                                     (self.v, self.v_scale, v_col)):
            x = np.asarray(col, np.float32)
            need = np.max(np.abs(x), axis=2) / 127.0   # [L, H]
            cur = scale_arr[:, pid]
            grow = need > cur
            if np.any(grow):
                new = np.where(grow, need, cur)
                if off:
                    ratio = np.divide(cur, new,
                                      out=np.ones_like(cur),
                                      where=new > 0)
                    resident = slab[:, pid, :, :off].astype(np.float32)
                    slab[:, pid, :, :off] = np.rint(
                        resident * ratio[:, :, None, None]
                    ).astype(np.int8)
                scale_arr[:, pid] = new
                cur = new
            self._quantize_into(slab, scale_arr, pid,
                                slice(off, off + 1),
                                x[:, :, None, :], cur)

    # -- sequence lifecycle -------------------------------------------------
    def open_sequence(self, prompt: np.ndarray, k_prompt: np.ndarray,
                      v_prompt: np.ndarray,
                      table: Optional[PageTable] = None,
                      reserved: Optional[int] = None,
                      start: int = 0) -> PageTable:
        """Install a prefilled prompt: ``k_prompt``/``v_prompt`` are the
        per-layer stacked KV ``[L, H, p - start, Dh]`` and ``prompt``
        the FULL int64 token ids (the sharing key material).  Pages
        completing a prefix another live sequence already stored are
        SHARED (refcount bump, no write); the rest are written and
        registered.

        ``start`` is the reused-prefill entry point: a table that
        already holds ``start`` tokens of adopted radix pages
        (page-aligned) receives only the uncovered suffix's KV —
        prefix keys still hash the full prompt head, so suffix pages
        stay shareable."""
        prompt = np.ascontiguousarray(np.asarray(prompt, np.int64))
        p = int(prompt.size)
        T = self.page_tokens
        start = int(start)
        if start % T:
            raise ValueError(
                f"start={start} must be page-aligned ({T} tokens/page)")
        if table is None:
            if start:
                raise ValueError("suffix install needs the adopted table")
            table = self.reserve(self.pages_needed(p) if reserved is None
                                 else reserved)
        if start and (table.length != start
                      or len(table.pages) != start // T):
            raise ValueError(
                f"table holds {table.length} tokens / "
                f"{len(table.pages)} pages, expected {start} adopted")
        with self._mu:
            for a in range(start, p, T):
                b = min(a + T, p)
                # key = the exact token prefix this page completes; KV
                # col t is a pure function of tokens <= t, so equal
                # prefixes mean bitwise-equal page content
                key = prompt[:b].tobytes()
                pid = self._prefix.get(key)
                if pid is not None and self._refcount[pid] > 0:
                    self._incref(pid)
                    self.prefix_hits += 1
                    metrics.count("kv.prefix_hits")
                else:
                    pid = self._alloc(table)
                    self._store_page_chunk(
                        pid, b - a,
                        k_prompt[:, :, a - start: b - start],
                        v_prompt[:, :, a - start: b - start])
                    self._prefix[key] = pid
                    self._page_key[pid] = key
                table.pages.append(pid)
            table.length = p
        metrics.count("kv.append_bytes", k_prompt.nbytes + v_prompt.nbytes)
        self._publish()
        return table

    def append_column(self, table: PageTable, k_col: np.ndarray,
                      v_col: np.ndarray):
        """Write one decode step's KV column ``[L, H, Dh]`` at position
        ``table.length``.  Crossing a page boundary allocates a fresh
        exclusive page; writing into a shared page copies it first
        (copy-on-write) so sharers never see this sequence's tokens."""
        pos = table.length
        T = self.page_tokens
        j, off = pos // T, pos % T
        with self._mu:
            if off == 0:
                if j != len(table.pages):
                    raise ValueError(
                        f"page table corrupt: position {pos} expects "
                        f"page index {j}, table holds {len(table.pages)}")
                table.pages.append(self._alloc(table))
            pid = table.pages[j]
            if self._refcount[pid] > 1:
                new = self._alloc(table)
                self.k[:, new] = self.k[:, pid]
                self.v[:, new] = self.v[:, pid]
                if self.is_quantized:
                    self.k_scale[:, new] = self.k_scale[:, pid]
                    self.v_scale[:, new] = self.v_scale[:, pid]
                self._decref(pid)
                table.pages[j] = new
                pid = new
                self.cow_copies += 1
                metrics.count("kv.cow_copies")
            self._store_column(pid, off, k_col, v_col)
            table.length = pos + 1
        metrics.count("kv.append_bytes", k_col.nbytes + v_col.nbytes)
        self._publish()

    def account_prompt(self, table: PageTable, n_tokens: int):
        """`open_sequence` for a device-only pool: the pages a prompt of
        `n_tokens` occupies are charged to `table` and nothing is stored
        or registered (its KV went from the prefill program into the state
        slots' arrays, on the device)."""
        with self._mu:
            for _ in range(self.pages_needed(n_tokens) - len(table.pages)):
                table.pages.append(self._alloc(table))
            table.length = int(n_tokens)
        self._publish()
        return table

    def account_column(self, table: PageTable):
        """`append_column` for a device-only pool: one more token, a fresh
        page when it crosses a boundary (the decode program wrote the
        column where the device arrays lie)."""
        with self._mu:
            if table.length % self.page_tokens == 0:
                table.pages.append(self._alloc(table))
            table.length += 1
        self._publish()

    def gather(self, table: PageTable):
        """Dense per-layer KV view of one sequence: ``(k, v)`` each
        ``[L, H, length, Dh]`` — the gather-by-page-table read the
        decode step feeds into the model's existing cache path (compiled
        shapes never see page structure)."""
        if self.device_only:
            raise RuntimeError("a device-only pool stores no pages to "
                               "gather: the KV is StateSlots' arrays")
        L, H, T, D = (self.num_layers, self.num_heads, self.page_tokens,
                      self.head_dim)
        out_dtype = np.float32 if self.is_quantized else self.dtype
        if not table.pages:
            return (np.zeros((L, H, 0, D), out_dtype),
                    np.zeros((L, H, 0, D), out_dtype))
        idx = np.asarray(table.pages, np.int64)
        n = idx.size
        k = self.k[:, idx]
        v = self.v[:, idx]
        if self.is_quantized:
            # dequantize through the per-(layer, page, head) sidecar:
            # x = q * scale, broadcast over the token and Dh dims
            k = k.astype(np.float32) \
                * self.k_scale[:, idx][:, :, :, None, None]
            v = v.astype(np.float32) \
                * self.v_scale[:, idx][:, :, :, None, None]
        k = k.transpose(0, 2, 1, 3, 4).reshape(L, H, n * T, D)
        v = v.transpose(0, 2, 1, 3, 4).reshape(L, H, n * T, D)
        metrics.count("kv.gather_bytes", k.nbytes + v.nbytes)
        return k[:, :, : table.length], v[:, :, : table.length]

    def close_sequence(self, table: PageTable):
        """Retire a sequence THE MOMENT it finishes: drop every page
        refcount (freeing pages nobody else shares) and return the
        unconsumed reservation."""
        with self._mu:
            for pid in table.pages:
                self._decref(pid)
            table.pages = []
            table.length = 0
        self.release(table)
        self._publish()

    # -- observability ------------------------------------------------------
    def stats(self) -> Dict:
        """The /stats + bench payload: geometry, occupancy, sharing."""
        with self._mu:
            free = len(self._free)
            shared = self.pages_shared
            return {
                "pages_total": self.num_pages,
                "pages_free": free,
                "pages_used": self.num_pages - free,
                "pages_reserved": self._reserved_unallocated,
                "pages_shared": shared,
                "pages_retained": self.pages_retained,
                "page_tokens": self.page_tokens,
                "page_bytes": self.page_bytes,
                "tp_degree": self.tp_degree,
                "page_bytes_per_chip": self.page_bytes_per_chip,
                "prefix_hits": self.prefix_hits,
                "cow_copies": self.cow_copies,
                "kv_dtype": self.dtype.name,
                "quant_scale_clips": self.quant_scale_clips,
                "occupancy": round(1.0 - free / self.num_pages, 4),
                **({"state_slots_total": self.state.slots,
                    "state_slots_used": self.state.used,
                    "state_bytes": self.state.nbytes,
                    "kv_device_bytes": self.state.kv_slot_bytes
                    * self.state.slots}
                   if self.state is not None else {}),
            }

    def _publish(self):
        """Keep the autoscaler-facing gauges current (scraped through
        monitor.prometheus_text by the server's /metrics)."""
        metrics.gauge("kv.pages_total", self.num_pages)
        metrics.gauge("kv.pages_free", len(self._free))
        metrics.gauge("kv.pages_shared", self.pages_shared)
        metrics.gauge("kv.pages_reserved", self._reserved_unallocated)
        metrics.gauge("kv.retained_pages", self.pages_retained)
        # dtype as a numeric gauge (Prometheus has no string series):
        # 1 = int8 pages, 0 = fp pages; the clip counter rides beside
        # it so a saturating pool is visible even before /stats is read
        metrics.gauge("kv.kv_dtype_int8", 1 if self.is_quantized else 0)
        metrics.gauge("kv.quant_scale_clips", self.quant_scale_clips)

    def assert_drained(self):
        """Post-drain leak check: every page free OR retained-by-radix
        (pinned with no live sequence — clean residency, not a leak),
        nothing reserved, and no prefix registered for a page that is
        neither free, live, nor radix-pinned (tests + engine stop-path
        sanity)."""
        with self._mu:
            leaked = [pid for pid in range(self.num_pages)
                      if self._refcount[pid] > 0
                      and not (pid in self._radix_pinned
                               and self._refcount[pid] == 1)]
            stale = [k for k, pid in self._prefix.items()
                     if pid not in self._radix_pinned]
            if self.state is not None and self.state.used:
                raise AssertionError(
                    f"state leak: {self.state.used} of {self.state.slots} "
                    "state slots held by retired sequences")
            if leaked or self._reserved_unallocated or stale:
                raise AssertionError(
                    f"page leak: {len(leaked)} pages held by retired "
                    f"sequences (neither free, live, nor radix-pinned), "
                    f"{self._reserved_unallocated} reserved, "
                    f"{len(stale)} prefixes registered for unpinned "
                    f"pages")


def budget_drift(pool: PagedKVPool, model=None) -> List[str]:
    """Re-derive the pool's recorded ``static.page_budget`` plan and
    report every way the live geometry disagrees — the serving analog
    of the verifier's V504 plan-drift check (a hand-resized pool stops
    matching what the HBM walker sized, and this makes it visible
    instead of silently mis-budgeted)."""
    if pool.plan is None:
        return ["pool carries no recorded plan (hand-built, not "
                "page_budget-sized)"]
    from ..static.planner import page_budget
    plan = pool.plan
    fresh = page_budget(
        model, config=plan.get("config"),
        page_tokens=int(plan["page_tokens"]),
        # the PRE-clamp requested context: re-deriving from the clamped
        # value would shift the workspace split and cry wolf
        max_context=int(plan.get("max_context_requested",
                                 plan["max_context"])),
        hbm_bytes=int(plan["hbm_bytes"]),
        # weight_bytes_fp32 is the RAW parameter-byte input; feeding the
        # int8-adjusted resident bytes back would re-quantize them
        weight_bytes=(int(plan.get("weight_bytes_fp32",
                                   plan["weight_bytes"]))
                      if model is None else None),
        max_slots_cap=int(plan.get("max_slots_cap", 0)) or None,
        headroom=float(plan.get("headroom", 0.08)),
        draft_layers=int(plan.get("draft_layers", 0)),
        tp_degree=int(plan.get("tp_degree", 1)),
        kv_dtype=str(plan.get("kv_dtype", "float32")),
        weight_dtype=str(plan.get("weight_dtype", "float32")))
    drift = []
    want_dtype = np.dtype(str(plan.get("kv_dtype", "float32")))
    if pool.dtype != want_dtype:
        drift.append(
            f"kv_dtype: pool stores {pool.dtype.name}, plan records "
            f"{want_dtype.name} — the carve assumed "
            f"{want_dtype.itemsize}-byte pages")
    have = pool.state.slot_bytes if pool.state is not None else 0
    want = int(fresh.get("state_slot_bytes", 0)) \
        + int(fresh.get("kv_slot_bytes", 0))
    if want != have:
        drift.append(
            f"state_slot_bytes: pool holds {have} B a slot (device KV and "
            f"state), page_budget derives {want}")
    if pool.state is not None and pool.state.slots != int(
            fresh["max_slots"]):
        drift.append(
            f"state slots: pool has {pool.state.slots}, page_budget "
            f"derives {fresh['max_slots']} under the recorded inputs")
    for key, live in (("pages", pool.num_pages),
                      ("page_tokens", pool.page_tokens),
                      ("num_layers", pool.num_layers),
                      ("num_heads", pool.num_heads),
                      ("head_dim", pool.head_dim)):
        if int(fresh[key]) != int(live):
            drift.append(
                f"{key}: pool has {live}, page_budget derives "
                f"{fresh[key]} under the recorded inputs")
    # retention watermarks ride the plan (prefix_cache reads them);
    # hand-edited watermarks are drift exactly like hand-set pages
    if plan.get("retained_watermarks") is not None:
        for key in ("low", "high"):
            want = int(fresh["retained_watermarks"][key])
            have = int(plan["retained_watermarks"].get(key, -1))
            if want != have:
                drift.append(
                    f"retained_watermarks.{key}: plan records {have}, "
                    f"page_budget derives {want}")
    return drift
