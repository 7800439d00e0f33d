"""Per-operator runtime statistics + the query timeline profiler's span sink
(reference: daft-local-execution/src/runtime_stats — rows/CPU per pipeline
node feeding progress bars, subscribers, and EXPLAIN ANALYZE).

The executor asks current_collector() per query; when a collector is active
(subscribers attached or explain_analyze running) every physical node's
output iterator is wrapped to count rows/batches and attribute self-time.
When inactive the executor takes its zero-overhead path.

Wall-clock attribution (the profiler tentpole): an operator's attributed
self time splits three ways —

- compute: time its own body spent producing (total next() time minus nested
  same-thread children minus channel starvation),
- starve: time blocked pulling from an UPSTREAM stage channel that had
  nothing ready (pipeline.Channel get-side, attributed to the consumer node
  active on that thread),
- blocked: time the operator's stage thread spent blocked pushing into a
  FULL downstream channel (pipeline.Channel put-side backpressure, attributed
  to the channel's producer node).

seconds == compute + starve + blocked by construction, so EXPLAIN ANALYZE's
stall columns always reconcile with the self-time column.

SpanRecorder is the timeline profiler's sink: coarse wall-clock spans
(the query's life from plan to result encoding, device dispatch and what the
host does inside it, H2D/D2H transfer, coalescer flushes, shuffle fetches)
recorded by the engine only while a recorder is installed — the no-recorder
path is a single attribute read and one shared no-op context manager,
preserving the zero-overhead guarantee. One process-wide slot (like
distributed.shuffle's ShuffleRecorder): workers run one task at a time and
the driver profiles one query at a time.

Spans form one tree per query: every recorded span carries `id`, `parent`
(the innermost span open in the same context, 0 for a root) and the `qid` of
its query in its `args`; the sink's call stays record(name, cat, t0, t1,
args). `t0`/`t1` are `time.time()`; for the same extent a
`jax.profiler.TraceAnnotation` of the span's name is open, so a profiler
capture holds the program's spans on its own clock above the device
operations they caused.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
import uuid
from contextlib import contextmanager
from typing import Dict, List, Optional

from .events import OperatorStats
from .metrics import registry

_local = threading.local()


class StatsCollector:
    def __init__(self) -> None:
        # nid -> [name, rows, batches, total_seconds, child_seconds,
        #         starve_seconds, blocked_seconds]
        self._nodes: Dict[int, list] = {}
        # stable per-query sequential node ids: keyed off id(node) for O(1)
        # lookup, but every wrapped node is ANCHORED (strong ref) for the
        # collector's lifetime so CPython can never reuse a freed node's id
        # mid-query and silently merge two operators' stats (the id()-reuse
        # bug class fixed for _decision_key in the residency manager)
        self._ids: Dict[int, int] = {}
        self._anchors: List[object] = []
        self._seq = 0
        # nid -> execution-path annotation (e.g. "mesh: 8 devices"), rendered
        # as a suffix on the operator name in EXPLAIN ANALYZE
        self._notes: Dict[int, str] = {}

    def node_id(self, node) -> int:
        """Stable sequential id for `node` within this collector (1-based in
        wrap order — deterministic across identical runs, unlike id())."""
        nid = self._ids.get(id(node))
        if nid is None:
            self._seq += 1
            nid = self._seq
            self._ids[id(node)] = nid
            self._anchors.append(node)
        return nid

    def wrap(self, node, iterator):
        """Wrap one operator's output iterator with row/time accounting.

        Attributed time is SELF time: total time blocked in this operator's
        next() minus time its direct children spent producing for it.
        """
        nid = self.node_id(node)
        entry = self._nodes.setdefault(
            nid, [node.name(), 0, 0, 0.0, 0.0, 0.0, 0.0])

        def gen():
            while True:
                t0 = time.perf_counter()
                prev = getattr(_local, "active", None)
                _local.active = nid
                try:
                    part = next(iterator)
                except StopIteration:
                    _local.active = prev
                    dt = time.perf_counter() - t0
                    entry[3] += dt
                    if prev is not None and prev in self._nodes:
                        self._nodes[prev][4] += dt
                    return
                finally:
                    _local.active = prev
                dt = time.perf_counter() - t0
                entry[3] += dt
                if prev is not None and prev in self._nodes:
                    self._nodes[prev][4] += dt
                entry[1] += part.num_rows
                entry[2] += 1
                yield part

        return gen()

    # ---- stall attribution (called by pipeline.Channel) --------------------------
    def note_starve(self, seconds: float) -> None:
        """Upstream starvation: the calling thread's active node spent
        `seconds` blocked on an empty stage channel. The wait happened inside
        that node's next() window, so it is carved OUT of compute at finish()."""
        nid = getattr(_local, "active", None)
        if nid is not None:
            entry = self._nodes.get(nid)
            if entry is not None:
                entry[5] += seconds

    def note_blocked(self, nid: int, seconds: float) -> None:
        """Downstream backpressure: node `nid`'s stage thread spent `seconds`
        blocked pushing into a full channel. Happens OUTSIDE the node's
        next() window (the producer loop), so finish() adds it on top."""
        entry = self._nodes.get(nid)
        if entry is not None:
            entry[6] += seconds

    def annotate(self, node, note: str) -> None:
        """Attach an execution-path note to one operator ("mesh: 8 devices");
        EXPLAIN ANALYZE renders it beside the operator name so the chosen
        tier is visible in the report, not only in the counters."""
        self._notes[self.node_id(node)] = note

    def finish(self) -> List[OperatorStats]:
        out = []
        for nid, (name, rows, batches, total, child, starve,
                  blocked) in self._nodes.items():
            compute = max(total - child - starve, 0.0)
            note = self._notes.get(nid)
            if note:
                name = f"{name} [{note}]"
            out.append(OperatorStats(
                node_id=nid, name=name, rows_out=rows, batches_out=batches,
                seconds=compute + starve + blocked,
                compute_seconds=compute, starve_seconds=starve,
                blocked_seconds=blocked))
        return out


def current_collector() -> Optional[StatsCollector]:
    return getattr(_local, "collector", None)


def set_collector(c: Optional[StatsCollector]) -> None:
    _local.collector = c


# ---- timeline spans ------------------------------------------------------------------


class SpanRecorder:
    """Thread-safe wall-clock span sink for the query timeline profiler.

    Spans are plain dicts (picklable — workers ship them back in TaskResult):
    {"name", "cat", "ts": unix seconds, "dur": seconds, "args": {...}}.
    Bounded: past `cap` spans the recorder counts drops instead of growing —
    a pathological query must never OOM the profiler.
    """

    def __init__(self, cap: int = 8192):
        self._lock = threading.Lock()
        self._spans: List[dict] = []
        self.cap = cap
        self.dropped = 0

    def record(self, name: str, cat: str, t0: float, t1: float,
               args: Optional[dict] = None) -> None:
        span = {"name": name, "cat": cat, "ts": t0, "dur": max(t1 - t0, 0.0)}
        if args:
            span["args"] = args
        with self._lock:
            if len(self._spans) >= self.cap:
                self.dropped += 1
                return
            self._spans.append(span)

    def drain(self) -> List[dict]:
        with self._lock:
            spans, self._spans = self._spans, []
            return spans


# process-global active span recorder (None = profiling off everywhere; the
# engine's instrumentation sites pay one module-attribute read)
_ACTIVE_SPANS: Optional[SpanRecorder] = None

# per-thread override sentinel: a thread inside span_scope() reads its own
# slot INSTEAD of the global one, so concurrent serving queries can isolate
# themselves from a query being profiled elsewhere in the process (their
# device spans must not bleed into that query's recorder, and vice versa)
_UNSET = object()
# how many span_scope() overrides are open on any thread: while it is 0 (and
# no global recorder is set) profile_span answers without a thread-local read
_SCOPED = 0
_SCOPED_LOCK = threading.Lock()


def current_spans() -> Optional[SpanRecorder]:
    rec = getattr(_local, "spans", _UNSET)
    if rec is not _UNSET:
        return rec
    return _ACTIVE_SPANS


def set_spans(rec: Optional[SpanRecorder]) -> None:
    global _ACTIVE_SPANS
    _ACTIVE_SPANS = rec


@contextmanager
def span_scope(rec: Optional[SpanRecorder]):
    """Thread-scoped span recorder override: inside the scope, THIS thread's
    instrumentation sites record into `rec` (or nowhere, for rec=None)
    regardless of the process-global slot. ServingSession worker threads run
    queries under span_scope(None) so a concurrently-profiled query's global
    recorder never receives another tenant's spans. Spans recorded from
    pipeline stage/pool threads still follow the global slot — serving
    documents that per-query profiling is a serialized, opt-in path."""
    global _SCOPED
    prev = getattr(_local, "spans", _UNSET)
    with _SCOPED_LOCK:
        _SCOPED += 1
    _local.spans = rec
    try:
        yield
    finally:
        if prev is _UNSET:
            del _local.spans
        else:
            _local.spans = prev
        with _SCOPED_LOCK:
            _SCOPED -= 1


# ---- the span tree ------------------------------------------------------------------
# (qid, id of the innermost open span) of the calling context. A contextvars
# variable, not a thread-local: the threads a query starts (pipeline stage
# threads, pool morsels) run in a copy of the context that started them, so
# their spans hang under the operator that spawned them.
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "daft_tpu_span", default=None)
_SPAN_IDS = itertools.count(1)
_trace_annotation = None  # jax.profiler.TraceAnnotation, once a span records


class _NoSpan:
    """What `profile_span` returns while nothing records: one shared object,
    no clock read, no allocation."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _Span:
    """One recording span. `open`/`close` take the clocks and give the span
    its place in the tree (`id`, `parent`, `qid` in `args`); `__enter__`
    additionally makes it the innermost open span of the context (`_ctx`).
    With `rec=None` (`timed_span`, no recorder) it only measures `seconds`."""

    __slots__ = ("_rec", "_name", "_cat", "args", "_t0", "_ctx", "_token",
                 "_ann", "seconds")

    def __init__(self, rec, name: str, cat: str, args: dict):
        self._rec, self._name, self._cat, self.args = rec, name, cat, args
        self._ann = self._token = None
        self.seconds = 0.0

    def open(self) -> "_Span":
        if self._rec is not None:
            global _trace_annotation
            if _trace_annotation is None:
                from jax.profiler import TraceAnnotation

                _trace_annotation = TraceAnnotation
            cur = _CURRENT.get()
            args = self.args
            # a query names its own qid; below an open span the tree's wins
            qid = args.pop("qid", "")
            if cur is not None:
                qid = cur[0] or qid
            sid = next(_SPAN_IDS)
            args["id"], args["parent"], args["qid"] = \
                sid, (cur[1] if cur is not None else 0), qid
            self._ctx = (qid, sid)
            # the same extent on the profiler's own clock: the span sits in
            # the .xplane.pb above the device operations it caused
            self._ann = _trace_annotation(self._name)
            self._ann.__enter__()
        self._t0 = time.time()
        return self

    def close(self) -> None:
        t1 = time.time()
        self.seconds = t1 - self._t0
        if self._rec is not None:
            self._ann.__exit__(None, None, None)
            self._rec.record(self._name, self._cat, self._t0, t1, self.args)

    def __enter__(self) -> "_Span":
        self.open()
        if self._rec is not None:
            self._token = _CURRENT.set(self._ctx)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._token is not None:
            _CURRENT.reset(self._token)
        if exc_type is not None and self._rec is not None:
            self.args["error"] = exc_type.__name__
        self.close()
        return False


def profile_span(name: str, cat: str, **args):
    """Record the enclosed block as a timeline span when a SpanRecorder is
    active: `with profile_span(...) as sp` gives the span (`sp.args` takes
    what is known only at the end) and makes it the parent of every span
    opened inside, on this thread or on a thread started from it. With no
    recorder it returns one shared no-op (`sp` is None): no clock read, no
    record. Used at COARSE sites only (a device dispatch, a coalescer flush,
    a shuffle fetch), never per row."""
    if _ACTIVE_SPANS is None and not _SCOPED:
        return _NO_SPAN
    rec = current_spans()
    if rec is None:
        return _NO_SPAN
    return _Span(rec, name, cat, args)


class _ColdSpan(_Span):
    """A cold site (`timed_span(counter=...)`): a `_Span` whose self time, in
    microseconds, is added to a registry counter as it closes, recorder or
    not. A warm query opens none."""

    __slots__ = ("_counter", "_part")

    def __init__(self, rec, name: str, cat: str, args: dict, counter: str, part: bool):
        super().__init__(rec, name, cat, args)
        self._counter, self._part = counter, part

    def close(self) -> None:
        super().close()
        own = self.seconds if self._part \
            else cold_self_seconds(self._t0, self._t0 + self.seconds)
        registry().inc(self._counter, int(own * 1e6))


def timed_span(name: str, cat: str, counter: Optional[str] = None,
               part: bool = False, **args) -> _Span:
    """`profile_span` for the caller who needs the extent either way (an
    event that carries it): always measures `.seconds`, records only while a
    SpanRecorder is active.

    With `counter` the span is a COLD SITE: as it closes, its microseconds
    are added to that registry counter whether or not anything records, so
    that set-up, which runs before any recorder is installed, can be read
    from counters alone (total less what a window's executions added:
    benchmark/setup_counters.py). Two clock reads and an `inc` an event: a
    site may name a counter only if a warm execution over a resident table
    never reaches it (a column's first upload, a dictionary's first encode,
    a calibration, a residency miss); tests/test_cold_sites.py holds every
    such counter at a delta of 0 across a repeat query.

    The counters are self times, so they add up: a cold site counts its
    extent less the cold extents inside it on the same thread (an upload
    inside a residency build, a program built inside a calibration:
    `cold_self_seconds`). With `part=True` the site is a part of the cold
    site around it, which keeps its whole: the part counts its own extent
    and takes nothing from it (`h2d_prepare_us` inside `h2d_upload_us`)."""
    if counter is None:
        return _Span(current_spans(), name, cat, args)
    return _ColdSpan(current_spans(), name, cat, args, counter, part)


_COLD_KEPT = 1024


def cold_self_seconds(start: float, end: float) -> float:
    """What a cold extent of this thread, heard of as it ends (`time.time()`
    readings), may count: its length less the cold extents inside it, which
    counted themselves. The one place self time is taken, for the sites that
    are spans and for the program builds JAX reports (utils/jax_setup), so a
    second is counted once whichever kind encloses the other. A thread's
    extents nest properly, so those inside this one are the last ones heard
    that ended after it began; it then stands for them all."""
    done = getattr(_local, "cold", None)  # [(start, end)], by end
    if done is None:
        done = _local.cold = []
    inside = 0.0
    while done and done[-1][1] > start:
        a, b = done.pop()
        inside += b - a
    done.append((start, end))
    del done[:-_COLD_KEPT]
    return max(end - start - inside, 0.0)


def record_span(name: str, cat: str, t0: float, t1: float, **args) -> None:
    """Record a span whose extent was measured elsewhere (a listener told
    how long a compile took), as a leaf under the context's open span."""
    rec = current_spans()
    if rec is None:
        return
    cur = _CURRENT.get()
    args["id"] = next(_SPAN_IDS)
    args["parent"], args["qid"] = (cur[1], cur[0]) if cur is not None else (0, "")
    rec.record(name, cat, t0, t1, args)


def current_qid() -> str:
    """The qid of the span tree open in this context ("" outside one)."""
    cur = _CURRENT.get()
    return cur[0] if cur is not None else ""


class _QidScope:
    __slots__ = ("_token",)

    def __enter__(self):
        self._token = _CURRENT.set((uuid.uuid4().hex[:12], 0))

    def __exit__(self, *exc) -> bool:
        _CURRENT.reset(self._token)
        return False


def qid_scope():
    """Name a qid for the root spans opened inside (a query and the encoding
    of its result share one) without opening a span. No-op with no recorder
    or inside an open tree."""
    if current_spans() is None or _CURRENT.get() is not None:
        return _NO_SPAN
    return _QidScope()


def span_iter(name: str, cat: str, inner, **args):
    """Stream `inner` through as-is; while a SpanRecorder is active, record
    ONE span covering the whole consumption window (first pull to exhaustion
    or consumer close), with rows/batches accumulated into the span args on
    top of the caller's. The span is the context's innermost one during each
    pull of `inner` and never across a yield, so nested streams and the
    consumer's own spans keep their true parents. With no recorder this
    returns `inner` itself — the streaming counterpart of profile_span,
    shared by the operator, scan and shuffle read/fetch sites."""
    if _ACTIVE_SPANS is None and not _SCOPED:
        return inner
    rec = current_spans()
    if rec is None:
        return inner
    return _span_iter(_Span(rec, name, cat, args), inner)


def _span_iter(span: _Span, inner):
    span.open()
    rows = batches = 0
    it = iter(inner)
    try:
        while True:
            # innermost during the pull, never across the yield
            token = _CURRENT.set(span._ctx)
            try:
                part = next(it)
            except StopIteration:
                return
            finally:
                _CURRENT.reset(token)
            rows += part.num_rows
            batches += 1
            yield part
    except Exception as e:
        span.args["error"] = type(e).__name__
        raise
    finally:
        close = getattr(it, "close", None)
        if close is not None:  # a consumer that left early: unwind upstream
            token = _CURRENT.set(span._ctx)  # inside the span, as exhaustion would
            try:
                close()
            finally:
                _CURRENT.reset(token)
        span.args["rows"], span.args["batches"] = rows, batches
        span.close()


def format_stats(stats: List[OperatorStats], total_seconds: float) -> str:
    lines = [f"{'operator':<24} {'rows out':>12} {'batches':>8} "
             f"{'self time':>10} {'compute':>10} {'starve':>10} {'blocked':>10}"]
    for s in sorted(stats, key=lambda s: -s.seconds):
        lines.append(
            f"{s.name:<24} {s.rows_out:>12} {s.batches_out:>8} "
            f"{s.seconds * 1000:>8.1f}ms {s.compute_seconds * 1000:>8.1f}ms "
            f"{s.starve_seconds * 1000:>8.1f}ms "
            f"{s.blocked_seconds * 1000:>8.1f}ms")
    lines.append(f"{'TOTAL':<24} {'':>12} {'':>8} {total_seconds * 1000:>8.1f}ms")
    return "\n".join(lines)
