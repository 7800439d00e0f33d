"""HBM residency manager: a budgeted, process-wide device-buffer cache.

The host side of the engine has a memory manager with spill
(execution/memory.py); this is its DEVICE-side counterpart. Every buffer the
engine keeps resident in HBM across queries — column planes uploaded by
``Series.to_device_cached``, join index planes, packed dim matrices,
visibility planes, dictionary-code planes (ops/device_join.py,
ops/grouped_stage.py) — is registered here instead of living in ad-hoc
``_device_cache`` dicts scattered over Series objects, so a long-lived session
has ONE place that knows how many device bytes the engine holds and can give
some back.

Design:

- Entries are keyed by (identity of the anchor's DATA, structural key). The
  anchor is the Series the cached value derives from. A column that is no
  view is identified by its token: a monotonic int (never reused, unlike
  CPython ``id``). A zero-copy view of a column (``Series.slice``: the
  morsels a pipeline cuts a resident table into on every query) is
  identified by its lineage, (token of the root column, row offset, length),
  so a fresh view object finds the slots its rows built before, and an entry
  lives as long as the ROOT does. Entries additionally carry a ``deps``
  tuple compared on lookup: Series by the same data identity (stored as
  tokens, which are never reused, so nothing is kept alive and a freed
  object can never alias a new one), anything else by object IDENTITY with a
  strong ref held in the entry. An optional ``literals`` tuple is compared by
  VALUE — query-shape caches key on the filter
  STRUCTURE and store the literals, so a session issuing the same query with
  varying predicate literals reuses one slot per shape instead of
  accumulating one entry per literal (ADVICE r5 medium).

- Byte accounting walks each entry's value and sums what its jax.Arrays hold
  on one device (device_nbytes: a plane sharded over a mesh counts a shard, a
  replicated one a copy), because the budget is one device's HBM; host numpy
  arrays are free — they are the host memory manager's problem.
  Values that lazily materialize device planes after being stored (e.g. the
  factorized-codes holder in device_join) are re-measured on every cache hit,
  so accounting converges without a registration protocol.

- Budget: ``DAFT_TPU_HBM_BUDGET`` / ExecutionConfig.hbm_budget_bytes.
  Positive = bytes; 0 (default) = auto, a fraction of
  ``jax.Device.memory_stats()['bytes_limit']`` when the backend reports it,
  else unbounded; negative = unbounded. Over budget, entries are evicted
  (recency-bucketed LRU, cheapest-to-rebuild first): the EVICT_BUCKET
  least-recently-used unpinned entries are weighed by estimated rebuild cost
  (upload bytes / bandwidth + host factorize time, ops/costmodel.py
  rebuild_cost_estimate) so re-uploadable column planes shed before join
  index planes of similar age. Eviction drops the registry reference; XLA
  frees the HBM when the last reference dies.

- Stable keys: deps-free slots carry a content-derived 64-bit key
  (stable_slot_key) identical across processes. They power (a) worker-side
  slot REBINDING — a repeat distributed sub-plan's freshly-unpickled columns
  hit the planes the previous task uploaded — and (b) the heartbeat digest()
  that the distributed scheduler intersects with sub-plan fingerprints for
  cache-affinity placement (distributed/affinity.py).

- Pinning: ``pin_scope()`` brackets one query execution. Entries touched
  inside the scope are pinned until scope exit and never evicted mid-query,
  so a tiny budget degrades to per-query working-set residency instead of
  evicting buffers an in-flight program still needs (and the byte accounting
  staying honest while it happens).

- Observability: hbm_cache_hits / hbm_cache_misses / hbm_lineage_hits (hits
  under another object than the one the entry was built under: what only
  lineage could find) / hbm_literal_rebuilds (an entry found under its key
  whose literals differed and which is therefore rebuilt in place: what a
  query's value costs where no program takes it as an argument) /
  hbm_evictions / hbm_eviction_bytes / hbm_pins counters plus hbm_bytes_resident /
  hbm_bytes_high_water gauges in the process metrics registry
  (observability/metrics.py), so per-query deltas land in QueryEnd.metrics,
  EXPLAIN ANALYZE's engine-counter table and worker heartbeats.

Zero-overhead contract: a host-only query never touches the manager (nothing
imports jax here; entries only appear when a device path uploads), and lookup
cost is one dict probe + identity compares: a column's content is hashed
(stable keys) only once the identity probe has missed.
"""

from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

from ..observability.metrics import registry
from ..observability.runtime_stats import timed_span

# ---- identity tokens ---------------------------------------------------------------

_token_lock = threading.Lock()
_token_counter = itertools.count(1)


def identity_token(obj) -> int:
    """Monotonic identity token for a long-lived engine object (Series,
    MicroPartition). Unlike ``id()``, tokens are never reused after GC, so
    caches keyed on them cannot silently alias a new object to a dead one
    (ADVICE r5 low: the executor's cost-decision cache did exactly that)."""
    tok = getattr(obj, "_rtoken", None)
    if tok is not None:
        return tok
    with _token_lock:
        tok = getattr(obj, "_rtoken", None)
        if tok is None:
            tok = next(_token_counter)
            try:
                object.__setattr__(obj, "_rtoken", tok)
            except AttributeError:
                # object without the slot: degrade to id() (advisory callers only)
                return id(obj)
        return tok


def data_identity(obj):
    """(owner, ident) of the data `obj` holds. A zero-copy view of a column
    (``Series.lineage``) is identified by (token of its root, offset, length)
    and owned by the root; a view of all of its root, and any object that is
    no view, by the token of the column itself. `ident` is what slots are
    keyed on and Series deps compared by; `owner` is the object whose
    lifetime an entry follows."""
    lineage = getattr(obj, "lineage", None)
    if lineage is not None:
        root, off = lineage()
        if root is not obj:
            n = len(obj)
            if off == 0 and n == len(root):
                return root, identity_token(root)
            return root, (identity_token(root), off, n)
    return obj, identity_token(obj)


class _SeriesDep:
    """A Series among an entry's deps, held as the identity of its data:
    tokens are never reused, so no strong ref is needed and none is kept."""

    __slots__ = ("ident",)

    def __init__(self, ident):
        self.ident = ident


def _dep_identities(deps) -> tuple:
    """Deps as an entry stores and compares them: a Series by the identity
    of its data, anything else (cached index arrays, tables) as itself."""
    return tuple(_SeriesDep(data_identity(d)[1])
                 if getattr(d, "lineage", None) is not None else d
                 for d in deps)


def _same_deps(stored: tuple, deps: tuple) -> bool:
    return len(stored) == len(deps) and all(
        a is b or (type(a) is _SeriesDep and type(b) is _SeriesDep
                   and a.ident == b.ident)
        for a, b in zip(stored, deps))


# ---- expression structure keys -----------------------------------------------------


def literal_nodes(expr) -> list:
    """The Literal nodes of `expr` in walk order: the order of
    expr_structure's literals, and the order in which a compiled device
    program numbers the slots it takes their values in
    (ops/device_eval.build_device_expr)."""
    from ..expressions.expressions import Literal

    return [node for node in expr.walk() if isinstance(node, Literal)]


def expr_structure(expr) -> Tuple[str, tuple]:
    """(skeleton, literals) for one expression: the skeleton is the repr with
    every literal masked, the literals are (dtype-repr, value) pairs in walk
    order. Two predicates differing only in literal values share a skeleton —
    the residency cache keys on the skeleton and compares the literals on
    lookup, so varying-literal queries reuse one slot per query shape; the
    aggregate stages key their compiled programs on the skeleton and the
    dtype reprs and take the values as arguments (ops/stage.py)."""
    from ..expressions.expressions import Literal

    lits = tuple((repr(node.dtype), node.value) for node in literal_nodes(expr))
    # an expression without literals is its own skeleton
    masked = expr.transform(
        lambda n: Literal("?") if isinstance(n, Literal) else None) if lits else expr
    return repr(masked), lits


def exprs_structure(exprs: Iterable) -> Tuple[tuple, tuple]:
    """(skeletons, literals) over a sequence of expressions (concatenated)."""
    skels = []
    lits: list = []
    for e in exprs:
        s, l = expr_structure(e)
        skels.append(s)
        lits.extend(l)
    return tuple(skels), tuple(lits)


# ---- stable slot keys --------------------------------------------------------------


def stable_slot_key(anchor, key: tuple) -> Optional[int]:
    """64-bit cross-process identity of one residency slot: a hash of the
    anchor's CONTENT fingerprint (Series.content_fingerprint) and the
    structural slot key. The same data under the same slot shape produces the
    same value in the driver and in every worker, so these keys are the
    vocabulary of the distributed cache-affinity protocol: workers publish
    digests of them in heartbeats, the planner fingerprints sub-plans with
    them, and the scheduler intersects the two. None = the anchor has no
    stable content identity (python-object column) — the slot stays
    identity-keyed only."""
    fp_fn = getattr(anchor, "content_fingerprint", None)
    if fp_fn is None:
        return None
    try:
        fp = fp_fn()
    except Exception:  # lint: ignore[broad-except] -- no stable key; slot stays anchor-scoped
        return None
    if fp is None:
        return None
    import hashlib

    h = hashlib.blake2b(digest_size=8)
    h.update(fp.to_bytes(8, "little"))
    h.update(repr(key).encode())
    return int.from_bytes(h.digest(), "little")


# ---- byte accounting ---------------------------------------------------------------


def device_nbytes(value) -> int:
    """Bytes that the jax device arrays reachable from `value` (tuples,
    lists, dicts, and objects exposing a ``device_nbytes()`` hook) hold on the
    device that holds most of them: the budget is one device's HBM
    (budget_bytes), so a plane row-sharded over a mesh of N devices counts a
    shard (1/N of its global bytes), a replicated plane one whole copy, a
    single-chip plane itself. Host numpy arrays count zero — the budget is
    HBM, not RAM."""
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        return 0
    arr_t = getattr(jax_mod, "Array", None)
    if arr_t is None:
        return 0
    total = 0
    stack = [value]
    while stack:
        x = stack.pop()
        if isinstance(x, arr_t):
            try:
                per_device: Dict[object, int] = {}
                for s in getattr(x, "addressable_shards", None) or ():
                    per_device[s.device] = per_device.get(s.device, 0) \
                        + int(s.data.nbytes)
                total += max(per_device.values()) if per_device \
                    else int(x.nbytes)
            except Exception:  # lint: ignore[broad-except] -- byte accounting is best-effort
                try:
                    total += int(x.nbytes)
                except Exception:  # lint: ignore[broad-except] -- unmeasurable value counts as 0
                    pass
        elif isinstance(x, (tuple, list)):
            stack.extend(x)
        elif isinstance(x, dict):
            stack.extend(x.values())
        else:
            hook = getattr(x, "device_nbytes", None)
            if hook is not None:
                try:
                    total += int(hook())
                except Exception:  # lint: ignore[broad-except] -- lazy-plane hook: best-effort bytes
                    pass
    return total


# ---- pin-scope observation (serving admission calibration) -------------------------

# Pin scopes open on whichever thread DRIVES a device stage — the session
# worker for simple plans, but usually a spawn_stage producer thread — so the
# observation handle lives in a module-level thread-local that
# pipeline.spawn_stage propagates to stage threads exactly like the ambient
# stats collector. One _PinObservation per observed query; stage threads are
# per-query (never pooled), so concurrent queries' scopes can't cross-note.
_OBS_TL = threading.local()


class _PinObservation:
    """Pinned-byte high-water across every pin scope of one query.

    A plan can hold SEVERAL scopes open at once (pipelined device stages on
    separate stage threads), so each exiting scope notes the sum over ALL of
    the observation's currently-open scopes — max-of-individual-scopes would
    under-state concurrent demand and mis-calibrate admission packing.
    ``open_scopes`` maps id(pinned set) -> pinned set; entries are added at
    scope entry (CPython dict set is atomic) and summed/removed under the
    manager lock at scope exit."""

    __slots__ = ("high_water", "open_scopes")

    def __init__(self) -> None:
        self.high_water = 0
        self.open_scopes: Dict[int, set] = {}

    def note(self, nbytes: int) -> None:
        if nbytes > self.high_water:
            self.high_water = nbytes


def current_pin_observation() -> Optional["_PinObservation"]:
    """This thread's active observation handle (None = not observing)."""
    return getattr(_OBS_TL, "obs", None)


def set_pin_observation(obs: Optional["_PinObservation"]) -> None:
    """Install `obs` as this thread's observation handle (stage threads call
    this with the handle captured at spawn time; None is a cheap no-op so
    unobserved pipelines pay nothing)."""
    if obs is not None:
        _OBS_TL.obs = obs


# ---- the manager -------------------------------------------------------------------


class _Entry:
    __slots__ = ("deps", "literals", "value", "nbytes", "pins", "anchor_ref",
                 "built_ref", "stable", "cost")

    def __init__(self, deps: tuple, literals, value, nbytes: int,
                 stable: Optional[int] = None, cost: float = 0.0):
        self.deps = deps
        self.literals = literals
        self.value = value
        self.nbytes = nbytes
        self.pins = 0
        self.anchor_ref = None  # keeps the death-callback weakref alive
        self.built_ref = None   # the object built under (hbm_lineage_hits)
        self.stable = stable    # cross-process slot key (None = identity-only)
        self.cost = cost        # estimated rebuild seconds (eviction ordering)


class _Miss:
    """A slot a lookup did not find: where its value goes once built."""

    __slots__ = ("owner", "anchor", "full_key", "deps", "literals", "stable")

    def __init__(self, owner, anchor, full_key: tuple, deps: tuple, literals,
                 stable: Optional[int]):
        self.owner = owner
        self.anchor = anchor
        self.full_key = full_key
        self.deps = deps
        self.literals = literals
        self.stable = stable


class ResidencyManager:
    """Process-wide registry of device-resident buffers with LRU eviction."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self._bytes = 0
        self._high_water = 0
        self._auto_budget: Optional[int] = None
        self._dead: list = []          # full keys whose anchor was collected
        self._tl = threading.local()   # active pin scopes (per thread)
        # stable slot key -> full key, for deps-free entries only: the
        # cross-process rebind index (distributed repeat sub-plans) and the
        # source of heartbeat digests
        self._stable: dict = {}
        # stable entries whose anchor died but were RETAINED (insertion-
        # ordered for FIFO capping): content-addressed planes a repeat
        # sub-plan can still rebind. Capped by DAFT_TPU_HBM_ORPHANS — 0
        # (default) keeps the strict die-with-your-anchor policy; the worker
        # pool opts its children in so planes survive between tasks.
        self._orphans: "OrderedDict[tuple, None]" = OrderedDict()
        self._orphan_cap: Optional[int] = None
        # admission controller state (serving tier): outstanding pin-scope
        # reservations of currently-admitted queries, token -> (tenant, bytes)
        self._adm = threading.Condition(threading.Lock())
        self._reservations: dict = {}
        self._rsv_seq = itertools.count(1)

    # ---- lookup / build ------------------------------------------------------------
    def get_or_build(self, anchor, key: tuple, deps: tuple,
                     build: Callable[[], Any], literals=None,
                     rebuild_rows: int = 0):
        """Return the cached value for (anchor, key), building it when absent.

        The slot is found by the identity of the anchor's DATA: a zero-copy
        view (a morsel of a resident column) by (root, offset, length), so a
        fresh view object of the same rows hits; anything else by its token.
        Hit requires every Series in `deps` to hold the same data by that
        rule, every other dep IDENTICAL to the stored one, and `literals`
        EQUAL to the stored ones; a mismatch rebuilds in place — the slot is
        reused, never duplicated.

        Deps-free slots (column planes, dictionary-code planes — values that
        are pure functions of the anchor's content) additionally carry a
        STABLE content-derived key: when the identity probe misses but an
        entry with the same stable key and equal literals exists, the slot is
        REBOUND to the new anchor instead of rebuilt — this is what lets a
        worker serve a repeat sub-plan's freshly-unpickled (new identity, same
        content) columns from HBM with zero re-upload.

        `rebuild_rows` is the host-side row count the build re-factorizes
        (dictionary codes, join indices); with the entry's device bytes it
        prices the rebuild for cost-weighted eviction."""
        hit, found = self._lookup(anchor, key, deps, literals)
        if hit:
            return found
        # outside the lock: builds may re-enter the manager. The span names
        # the slot kind (key[0]: "col", "didx", "pack", ...) behind a miss
        # (a cold site: `residency_build_us` is the build less the uploads,
        # encodes and program builds inside it, which count themselves)
        with timed_span("residency.build", "device", counter="residency_build_us",
                        slot=str(key[0])) as sp:
            value = build()
            nb = device_nbytes(value)
            sp.args["bytes"] = nb
        with self._lock:
            self._store(found, value, nb, rebuild_rows)
            self._note_bytes()
            self._evict_over_budget()
        return value

    def get_or_build_many(self, slots, build: Callable[[list], list]) -> list:
        """The values of the deps-free slots `slots`, (anchor, key,
        rebuild_rows) each, in their order: every slot is probed as
        get_or_build probes it (a hit counts, touches and pins as there), and
        the absent ones are built by ONE call, `build(indices into slots)`,
        which returns their values in that order, so that a caller can bring
        what the device lacks in one transfer. Each value is then an entry of
        its own, counted as a miss, pinned in the open scope and weighed
        against the budget like a slot get_or_build built; the books are
        brought up to date once for all of them."""
        out: list = [None] * len(slots)
        missed = []
        for i, (anchor, key, _rows) in enumerate(slots):
            hit, found = self._lookup(anchor, key, (), None)
            if hit:
                out[i] = found
            else:
                missed.append((i, found))
        if not missed:
            return out
        indices = [i for i, _miss in missed]
        with timed_span("residency.build", "device", counter="residency_build_us",
                        slot=str(slots[indices[0]][1][0]),
                        slots=len(indices)) as sp:
            values = build(indices)
            sizes = [device_nbytes(v) for v in values]
            sp.args["bytes"] = sum(sizes)
        with self._lock:
            for (i, miss), value, nb in zip(missed, values, sizes):
                self._store(miss, value, nb, slots[i][2])
                out[i] = value
            self._note_bytes()
            self._evict_over_budget()
        return out

    def in_transient_scope(self) -> bool:
        """True on a thread inside pin_scope(transient=True): what it builds
        belongs to morsels that die with the query."""
        return getattr(self._tl, "transient", False)

    def _lookup(self, anchor, key: tuple, deps: tuple, literals):
        """(True, value) where the slot is found, by identity or by a rebind
        of equal content; else (False, the _Miss _store files the built value
        under), with the miss counted."""
        owner, ident = data_identity(anchor)
        full_key = (ident, key)
        deps = _dep_identities(deps)
        with self._lock:
            self._sweep_dead()
            e = self._entries.get(full_key)
            if e is not None and _same_deps(e.deps, deps):
                if e.literals == literals:
                    return True, self._hit(full_key, e, anchor)
                # the slot of this query SHAPE holds another query's values:
                # what a value costs where it is no program's argument
                registry().inc("hbm_literal_rebuilds")
        # only now, the identity probe having missed, is the column hashed:
        # a hit never pays for a fingerprint (outside the lock: it reads the
        # whole column)
        stable = stable_slot_key(anchor, key) \
            if not deps and not self.in_transient_scope() else None
        if stable is not None:
            with self._lock:
                e = self._stable_rebind(stable, full_key, owner, anchor,
                                        literals)
                if e is not None:
                    registry().inc("hbm_cache_hits")
                    registry().inc("hbm_stable_rehits")
                    return True, e.value
        registry().inc("hbm_cache_misses")
        return False, _Miss(owner, anchor, full_key, deps, literals, stable)

    def _store(self, miss: "_Miss", value, nb: int, rebuild_rows: int) -> None:
        """File a built value under the slot `miss` names and pin it in the
        open scope (lock held; the caller notes the bytes and enforces the
        budget afterwards)."""
        from ..ops.costmodel import rebuild_cost_estimate

        full_key, stable = miss.full_key, miss.stable
        old = self._entries.pop(full_key, None)
        e = _Entry(miss.deps, miss.literals, value, nb, stable=stable,
                   cost=rebuild_cost_estimate(nb, rebuild_rows))
        if old is not None:
            self._bytes -= old.nbytes
            if old.stable is not None:
                self._stable.pop(old.stable, None)
            # rebuild-in-place: active pin scopes hold this slot by KEY —
            # the replacement inherits the pin count so it cannot be
            # evicted mid-query and scope exits balance exactly
            e.pins = old.pins
        if stable is not None:
            # a stale same-content slot under another identity (e.g. a
            # literal change arriving via a re-unpickled anchor) would
            # duplicate device bytes — drop it unless a query holds it
            prev_full = self._stable.get(stable)
            if prev_full is not None and prev_full != full_key:
                prev = self._entries.get(prev_full)
                if prev is not None and prev.pins == 0:
                    self._drop_entry(prev_full, prev)
            self._stable[stable] = full_key
        self._entries[full_key] = e
        self._bytes += nb
        self._watch_anchor(miss.owner, miss.anchor, full_key, e)
        self._pin(full_key, e)

    def _hit(self, full_key: tuple, e: _Entry, anchor):
        """The identity probe found the entry (lock held): re-measure (values
        may have lazily grown device planes), touch, pin, count."""
        nb = device_nbytes(e.value)
        if nb != e.nbytes:
            self._bytes += nb - e.nbytes
            e.nbytes = nb
            self._note_bytes()
        self._entries.move_to_end(full_key)
        self._pin(full_key, e)
        registry().inc("hbm_cache_hits")
        if e.built_ref is not None and e.built_ref() is not anchor:
            # another object than the one the entry was built under viewing
            # the same rows: a hit only lineage could find
            registry().inc("hbm_lineage_hits")
        return e.value

    def _stable_rebind(self, stable: int, full_key: tuple, owner, anchor,
                       literals) -> Optional[_Entry]:
        """Move a deps-free entry with matching content identity to a new
        anchor (called under the lock). Returns the entry on success."""
        prev_full = self._stable.get(stable)
        if prev_full is None or prev_full == full_key:
            return None
        e = self._entries.get(prev_full)
        # rebind only unpinned deps-free slots with equal literals: a pinned
        # slot is held by key in an active pin scope and must not be re-keyed
        if e is None or e.deps or e.pins != 0 or e.literals != literals:
            return None
        del self._entries[prev_full]
        self._orphans.pop(prev_full, None)  # re-anchored: no longer orphaned
        self._entries[full_key] = e
        self._stable[stable] = full_key
        nb = device_nbytes(e.value)
        if nb != e.nbytes:
            self._bytes += nb - e.nbytes
            e.nbytes = nb
            self._note_bytes()
        self._watch_anchor(owner, anchor, full_key, e)
        self._pin(full_key, e)
        return e

    def is_resident(self, anchor, key: tuple) -> bool:
        """Advisory residency probe for the cost model (no deps/literal check,
        no LRU touch, no counters): True when a buffer for this slot is
        currently registered, i.e. the h2d transfer for it is already paid."""
        full_key = (data_identity(anchor)[1], key)
        with self._lock:
            return full_key in self._entries

    # ---- pinning -------------------------------------------------------------------
    @contextlib.contextmanager
    def pin_scope(self, transient: bool = False):
        """Scope one query execution: every entry touched inside is pinned
        (never evicted) until exit; eviction re-runs at exit so the budget is
        re-enforced once the query's working set is released.

        `transient`: the stage is fed from a stream (a file scan), so the
        planes it builds belong to morsels that die with the query and that
        no later anchor can hold the content of: their builds skip the
        content fingerprint (`stable_slot_key` reads and hashes the whole
        column), which only a later rebind could repay."""
        scopes = getattr(self._tl, "scopes", None)
        if scopes is None:
            scopes = self._tl.scopes = []
        pinned: set = set()
        scopes.append(pinned)
        was_transient = getattr(self._tl, "transient", False)
        self._tl.transient = was_transient or transient
        obs = current_pin_observation()
        if obs is not None:
            # under the manager lock: concurrent scope EXITS iterate
            # open_scopes under that lock, and a bare dict insert mid-
            # iteration would raise (failing the query before its pins
            # decrement — permanently pinned HBM)
            with self._lock:
                obs.open_scopes[id(pinned)] = pinned
        try:
            yield self
        finally:
            scopes.pop()
            self._tl.transient = was_transient
            with self._lock:
                if obs is not None:
                    # admission calibration (serving/prepared.py): record the
                    # pinned bytes across ALL of the query's open scopes (this
                    # one included) so fingerprint-derived upper-bound
                    # reservations shrink toward observed CONCURRENT demand
                    keys = set().union(*obs.open_scopes.values())
                    obs.note(sum(
                        e.nbytes for k in keys
                        if (e := self._entries.get(k)) is not None))
                    obs.open_scopes.pop(id(pinned), None)
                for k in pinned:
                    e = self._entries.get(k)
                    if e is not None and e.pins > 0:
                        e.pins -= 1
                self._evict_over_budget()

    @contextlib.contextmanager
    def observe_pins(self):
        """Observe the pinned-byte high-water of every pin scope this query
        opens inside the context — on this thread AND on the stage threads
        its pipeline spawns (spawn_stage propagates the handle alongside the
        ambient stats collector, so the device stages' scopes are seen even
        though they run on producer threads). Yields a zero-arg callable
        returning the high-water so far; zero cost when not observing —
        pin_scope only sums bytes when a handle is installed."""
        prev = getattr(_OBS_TL, "obs", None)
        obs = _OBS_TL.obs = _PinObservation()
        try:
            yield lambda: obs.high_water
        finally:
            _OBS_TL.obs = prev

    def _pin(self, full_key: tuple, e: _Entry) -> None:
        scopes = getattr(self._tl, "scopes", None)
        if not scopes:
            return
        top = scopes[-1]
        if full_key not in top:
            top.add(full_key)
            e.pins += 1
            registry().inc("hbm_pins")

    # ---- admission control (serving tier) --------------------------------------------
    @contextlib.contextmanager
    def admit(self, est_bytes: int, tenant: str = "",
              tenant_budget: int = 0):
        """HBM admission controller: bracket one query's execution with a
        pin-scope byte RESERVATION. A query declares the device bytes its
        working set is estimated to pin (serving/prepared.py derives the
        estimate from the cost model's device-bytes probes via the plan
        fingerprint); admission waits while the SUM of currently-admitted
        reservations plus this one would exceed the HBM budget — queries
        queue instead of thrashing the LRU against each other's pinned
        planes. Yields True when the query had to wait (the caller's
        admission-wait attribution).

        Deadlock-free by construction: a query is ALWAYS admissible when no
        other reservation is outstanding, so a single query whose estimate
        exceeds the whole budget runs alone and degrades exactly like today
        (pin scope + eviction at scope exit) rather than waiting forever.
        `tenant_budget` > 0 additionally caps one tenant's concurrent
        reservations (config.tenant_budget_bytes), with the same
        no-outstanding-reservation escape per tenant. Estimates of 0 (host-
        only plans) admit immediately — the controller governs device
        working sets, not host compute."""
        est = max(int(est_bytes), 0)
        budget = self.budget_bytes()
        waited = False
        from ..cancellation import raise_if_cancelled

        with self._adm:
            while not self._admissible(est, tenant, budget, tenant_budget):
                # a cancelled query must not camp in the admission queue: the
                # raise unwinds BEFORE any reservation exists, so nothing
                # leaks (no-op for threads without a cancellation token)
                raise_if_cancelled("query cancelled while awaiting admission")
                if not waited:
                    waited = True
                    registry().inc("admission_waits_total")
                # timed wait: the budget is re-read so a config change (or an
                # auto-budget probe landing) unblocks waiters without a signal
                self._adm.wait(0.05)
                budget = self.budget_bytes()
            tok = next(self._rsv_seq)
            self._reservations[tok] = (tenant, est)
            registry().set_gauge(
                "hbm_reserved_bytes",
                float(sum(b for _t, b in self._reservations.values())))
        try:
            yield waited
        finally:
            with self._adm:
                self._reservations.pop(tok, None)
                registry().set_gauge(
                    "hbm_reserved_bytes",
                    float(sum(b for _t, b in self._reservations.values())))
                self._adm.notify_all()

    def _admissible(self, est: int, tenant: str, budget: int,
                    tenant_budget: int) -> bool:
        """Called under self._adm. The escape hatches (empty ledger / empty
        tenant ledger) are what make over-budget queries serialize instead of
        deadlock."""
        if est <= 0:
            return True
        if not self._reservations:
            return True
        if budget > 0 and sum(
                b for _t, b in self._reservations.values()) + est > budget:
            return False
        if tenant_budget > 0:
            mine = sum(b for t, b in self._reservations.values() if t == tenant)
            if mine and mine + est > tenant_budget:
                return False
        return True

    def reserved_bytes(self) -> int:
        """Outstanding admission reservations (introspection/tests)."""
        with self._adm:
            return sum(b for _t, b in self._reservations.values())

    def reservation_count(self) -> int:
        with self._adm:
            return len(self._reservations)

    # ---- budget / eviction ---------------------------------------------------------
    def budget_bytes(self) -> int:
        """Effective budget in bytes (0 = unbounded)."""
        from ..config import execution_config

        b = execution_config().hbm_budget_bytes
        if b > 0:
            return b
        if b < 0:
            return 0
        if self._auto_budget is None:
            self._auto_budget = self._probe_auto_budget()
        return self._auto_budget

    @staticmethod
    def _probe_auto_budget() -> int:
        jax_mod = sys.modules.get("jax")
        if jax_mod is None:
            return 0
        try:
            stats = jax_mod.devices()[0].memory_stats() or {}
            limit = int(stats.get("bytes_limit", 0) or 0)
            return (limit * 3) // 4 if limit > 0 else 0
        except Exception:  # lint: ignore[broad-except] -- backend without memory_stats: unbounded
            return 0

    # entries per recency bucket: eviction considers the least-recently-used
    # unpinned entries together (the OLDEST HALF of the registry, capped at
    # EVICT_BUCKET) and drops the cheapest-to-rebuild first, so a cold budget
    # squeeze sheds re-uploadable column planes before join index / dictionary
    # planes of similar age (strict LRU would drop whichever went longest
    # untouched, regardless of replacement price). Bounding the bucket to the
    # oldest HALF keeps recency meaningful: with two entries the pick is pure
    # LRU, so a hot cheap plane is never sacrificed to protect a cold
    # expensive one — that inversion would re-upload the hot plane every
    # query while the squatter never leaves.
    EVICT_BUCKET = 8

    def _evict_over_budget(self) -> None:
        budget = self.budget_bytes()
        if budget <= 0:
            return
        while self._bytes > budget:
            # front = least recently used; only UNPINNED entries count toward
            # the half, or pinned entries would pad the window into the
            # recency-hot tail and re-admit the inversion
            unpinned = [(k, e) for k, e in self._entries.items() if e.pins == 0]
            if not unpinned:
                return  # everything pinned: overshoot until the scope ends
            limit = min(self.EVICT_BUCKET, max(1, (len(unpinned) + 1) // 2))
            bucket = unpinned[:limit]  # oldest recency bucket
            victim_key, e = min(bucket, key=lambda kv: kv[1].cost)
            lru_cost = bucket[0][1].cost
            if e.cost < lru_cost:
                # rebuild seconds the pure-LRU victim would have cost, saved
                # by taking the cheaper entry instead (µs, monotone counter)
                registry().inc("hbm_evict_cost_saved",
                               int((lru_cost - e.cost) * 1e6))
            self._drop_entry(victim_key, e)
            registry().inc("hbm_evictions")
            registry().inc("hbm_eviction_bytes", e.nbytes)
        self._note_bytes()

    def _drop_entry(self, full_key: tuple, e: _Entry) -> None:
        """Remove one entry + its stable-index row; bytes accounting only
        (callers own counters/gauge refresh). Lock held by caller."""
        self._entries.pop(full_key, None)
        self._orphans.pop(full_key, None)
        self._bytes -= e.nbytes
        if e.stable is not None and self._stable.get(e.stable) == full_key:
            del self._stable[e.stable]

    def _note_bytes(self) -> None:
        if self._bytes > self._high_water:
            self._high_water = self._bytes
        registry().set_gauge("hbm_bytes_resident", float(self._bytes))
        registry().set_gauge("hbm_bytes_high_water", float(self._high_water))

    # ---- anchor lifetime -----------------------------------------------------------
    def _watch_anchor(self, owner, anchor, full_key: tuple,
                      e: _Entry) -> None:
        """The entry goes when `owner` is collected: the anchor itself, or
        the root column a view anchor views (a morsel object may die, its
        rows stay resident). `anchor` is remembered weakly as the object the
        entry was built (or rebound) under."""
        dead = self._dead

        def _on_collect(_ref, _key=full_key, _dead=dead):
            _dead.append(_key)  # list.append is atomic; processed under lock

        try:
            # the weakref must outlive the anchor for the callback to fire —
            # the entry itself holds it
            e.anchor_ref = weakref.ref(owner, _on_collect)
            e.built_ref = e.anchor_ref if anchor is owner \
                else weakref.ref(anchor)
        except TypeError:
            pass  # not weakref-able: entry lives until evicted by LRU

    def _sweep_dead(self) -> None:
        swept = False
        cap = self._orphan_budget()
        while self._dead:
            k = self._dead.pop()
            e = self._entries.get(k)
            if e is None:
                continue
            if cap > 0 and e.stable is not None and e.pins == 0:
                # content-addressed plane: the anchor is gone but identical
                # data (a repeat sub-plan's fresh unpickle) can still rebind
                # it — retain as an orphan, FIFO-capped below
                self._orphans[k] = None
                continue
            self._drop_entry(k, e)
            swept = True
        while len(self._orphans) > cap:
            k = next(iter(self._orphans))
            e = self._entries.get(k)
            if e is not None:
                self._drop_entry(k, e)
            else:
                self._orphans.pop(k, None)
            swept = True
        if swept:
            registry().set_gauge("hbm_bytes_resident", float(self._bytes))

    def _orphan_budget(self) -> int:
        """Max stable entries retained past their anchor's death
        (DAFT_TPU_HBM_ORPHANS, read once). 0 = strict anchor-coupled
        lifetime — the driver default, so dropping a host table still frees
        its device planes; WorkerPool sets a positive cap in worker
        environments so planes outlive the transient per-task plan objects."""
        if self._orphan_cap is None:
            from ..utils.env import env_int

            self._orphan_cap = env_int("DAFT_TPU_HBM_ORPHANS", 0, lo=0)
        return self._orphan_cap

    # ---- introspection -------------------------------------------------------------
    def digest(self, cap: int = 64) -> list:
        """Compact residency digest for heartbeats: up to `cap`
        (stable_slot_key, device_bytes) pairs, most-recently-used first.
        Only deps-free slots appear — they are the ones a repeat sub-plan can
        actually rebind to, so advertising anything else would overstate the
        transfer bytes a scheduler placement avoids."""
        out = []
        with self._lock:
            self._sweep_dead()
            for k in reversed(self._entries):
                e = self._entries[k]
                if e.stable is not None:
                    out.append((e.stable, e.nbytes))
                    if len(out) >= cap:
                        break
        return out

    def bytes_resident(self) -> int:
        with self._lock:
            self._sweep_dead()
            return self._bytes

    def entry_count(self) -> int:
        with self._lock:
            self._sweep_dead()
            return len(self._entries)

    def stats(self) -> dict:
        """Registry-consistent snapshot (the dashboard's /metrics, tests)."""
        reg = registry()
        with self._lock:
            self._sweep_dead()
            return {
                "hbm_bytes_resident": self._bytes,
                "hbm_bytes_high_water": self._high_water,
                "hbm_entries": len(self._entries),
                "hbm_cache_hits": reg.get("hbm_cache_hits"),
                "hbm_cache_misses": reg.get("hbm_cache_misses"),
                "hbm_lineage_hits": reg.get("hbm_lineage_hits"),
                "hbm_evictions": reg.get("hbm_evictions"),
                "hbm_eviction_bytes": reg.get("hbm_eviction_bytes"),
                "hbm_pins": reg.get("hbm_pins"),
                "hbm_stable_rehits": reg.get("hbm_stable_rehits"),
                "hbm_evict_cost_saved": reg.get("hbm_evict_cost_saved"),
            }

    def clear(self) -> None:
        """Drop every entry (test hook). Does not reset the registry counters
        — ops/counters.reset() owns those."""
        with self._lock:
            self._entries.clear()
            self._stable.clear()
            self._orphans.clear()
            self._dead.clear()
            self._bytes = 0
            self._high_water = 0
            self._auto_budget = None
            self._orphan_cap = None
            registry().set_gauge("hbm_bytes_resident", 0.0)
            registry().set_gauge("hbm_bytes_high_water", 0.0)


_MANAGER = ResidencyManager()


def manager() -> ResidencyManager:
    """The process-wide residency manager (one per driver / worker process)."""
    return _MANAGER
