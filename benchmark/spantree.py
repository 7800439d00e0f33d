"""The program's spans as a tree, by containment.

The harness's sink keeps `(name, unix start, unix end)` of every span the
program records (`run.py`'s `Spans`), without the `id`/`parent`/`qid` the
program puts in a span's arguments. What a reader needs of the tree can be
had from the extents alone, because a child lies within its parent:

- which execution a span belongs to: the one whose `unix_start`..`unix_end`
  holds it (`in_window`);
- a span's self time: the stretches over which it is the innermost span open
  (`owners`). Where spans of several threads overlap, a stretch goes to the
  one that began last, so every moment has one owner and self times add up to
  the time some span was open, never to more.

All times are seconds on one clock (the spans' own, unix).
"""

from __future__ import annotations

import heapq
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import xtrace as tr

Span = Tuple[str, float, float]
Names = Union[None, Sequence[str], Callable[[str], bool]]

# the root the program opens around every query since PR 25: a run whose spans
# lack it comes from a program without the span tree, and has nothing to read
ROOT = "query"


def _wanted(among: Names) -> Callable[[str], bool]:
    if among is None:
        return lambda name: True
    if callable(among):
        return among
    names = frozenset(among)
    return lambda name: name in names


def has_tree(spans: Iterable[Span]) -> bool:
    return any(name == ROOT for name, _a, _b in spans)


def in_window(spans: Iterable[Span], executions: Sequence[dict]) -> List[Span]:
    """The spans that lie within some execution of the window."""
    bounds = sorted((e["unix_start"], e["unix_end"]) for e in executions)
    out = []
    for s in spans:
        for lo, hi in bounds:
            if lo > s[1]:
                break
            if s[2] <= hi:
                out.append(s)
                break
    return out


def owners(spans: Iterable[Span], among: Names = None) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted (start, end, name) stretches: who owns each moment at
    which a span of `among` (all names where None) is open. The owner is the
    open span that began last: the innermost, on one thread."""
    want = _wanted(among)
    known = sorted((s for s in spans if want(s[0]) and s[2] > s[1]), key=lambda s: s[1])
    cuts = sorted({t for _n, a, b in known for t in (a, b)})
    out: List[Tuple[float, float, str]] = []
    heap: list = []  # (-start, order, end, name): the top began last
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(known) and known[k][1] <= a:
            name, start, end = known[k]
            heapq.heappush(heap, (-start, -k, end, name))
            k += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        if heap:
            name = heap[0][3]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def self_seconds(spans: Iterable[Span], among: Names = None) -> Dict[str, float]:
    """Seconds owned by each name (`owners`): its spans' length less what the
    spans of `among` opened inside them cover."""
    out: Dict[str, float] = {}
    for a, b, name in owners(spans, among):
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def covered(spans: Iterable[Span], among: Names = None) -> List[Tuple[float, float]]:
    """Disjoint, sorted intervals over which some span of `among` is open:
    spans that nest or overlap count once."""
    want = _wanted(among)
    return tr.union([(s[1], s[2]) for s in spans if want(s[0])])


def covered_seconds(spans: Iterable[Span], among: Names = None) -> float:
    return tr.length(covered(spans, among))


def ms_per_execution(ctx: dict, seconds_of: Callable[[List[Span]], float]) -> Optional[float]:
    """`seconds_of(the window's spans)` as milliseconds per execution; None
    where the program recorded no span tree."""
    if not has_tree(ctx["spans"]):
        return None
    runs = ctx["executions"]
    return 1e3 * seconds_of(in_window(ctx["spans"], runs)) / len(runs)


# the spans of one join dispatch: what the host does inside `device.dispatch`
# of the device join path. What nests deeper (a residency build, an upload)
# stays with the `join.*` span around it.
JOIN_PARTS = ("device.dispatch", "join.codes", "join.index", "join.gather", "device.launch")


def join_dispatches(spans: Sequence[Span]) -> List[Span]:
    """The `device.dispatch` spans that hold a `join.*` span."""
    joins = [(a, b) for n, a, b in spans if n.startswith("join.")]
    return [s for s in spans if s[0] == "device.dispatch"
            and any(s[1] <= a and b <= s[2] for a, b in joins)]


def join_part_ms(ctx: dict, name: str) -> Optional[float]:
    """The window's self time of `name` among `JOIN_PARTS`, in milliseconds
    per join dispatch (`join_dispatches`); None where no join dispatched."""
    spans = in_window(ctx["spans"], ctx["executions"])
    dispatches = len(join_dispatches(spans))
    if not dispatches:
        return None
    return 1e3 * self_seconds(spans, JOIN_PARTS).get(name, 0.0) / dispatches
