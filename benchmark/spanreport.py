"""What one traced run's spans say, for whoever writes `PERF.md`.

    python3 benchmark/spanreport.py .bench_trace/<cell>-<seed>

Reads what `run.py --trace 1` left in that directory (`reduced.json`: the
program's spans, the executions, the offset between the clocks; and the
`.xplane.pb`) and prints one JSON object:

- `templates`: for each template, its executions, their median time, and the
  milliseconds per execution each span name owns (`spantree.self_seconds`
  over all names: a span's time less what was opened inside it);
- `join_dispatch`: the same split inside the `device.dispatch` spans that
  hold a `join.*` span (`spantree.JOIN_PARTS`), as shares of their summed
  length; `device.dispatch` there is what no child covers;
- `residency_builds`: the `residency.build` spans of the window cannot name
  their slot here (the harness's sink drops span arguments): their count and
  seconds only;
- `clock_skew_us`: for every span of `--skew-span` (default
  `device.dispatch`), the start of its `TraceAnnotation` in the profile less
  its `time.time()` start moved onto the trace's clock by `to_trace`. That
  difference is how far `breakdown.idle_gaps` and every reader that ties a
  span to device time can be trusted.

The benchmark's runs never call it.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spantree  # noqa: E402
import xtrace as tr  # noqa: E402


def annotation_starts(xplane_path: str, name: str) -> list:
    """Sorted starts (seconds, the trace's clock) of the host-side events
    called `name`: the program's `TraceAnnotation`s."""
    from jax.profiler import ProfileData

    starts = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            continue
        for line in plane.lines:
            starts += [e.start_ns * 1e-9 for e in line.events if e.name == name]
    return sorted(starts)


def clock_skew_us(spans, to_trace: float, starts: list):
    """Each span's nearest annotation start less its own start on the trace's
    clock, in microseconds; None where the profile holds no such annotation."""
    if not starts:
        return None
    diffs = []
    for _name, t0, _t1 in spans:
        at = t0 + to_trace
        k = bisect.bisect_left(starts, at)
        near = min(starts[max(k - 1, 0):k + 1], key=lambda s: abs(s - at))
        diffs.append((near - at) * 1e6)
    mags = sorted(abs(d) for d in diffs)
    return {"spans": len(diffs), "annotations": len(starts),
            "median": statistics.median(diffs), "median_abs": statistics.median(mags),
            "max_abs": mags[-1]}


def report(log_dir: str, skew_span: str = "device.dispatch") -> dict:
    with open(os.path.join(log_dir, "reduced.json")) as f:
        reduced = json.load(f)
    spans = [tuple(s) for s in reduced["spans"]]
    runs = reduced["executions"]
    templates = {}
    for name in sorted({e["template"] for e in runs}):
        mine = [e for e in runs if e["template"] == name]
        own = spantree.self_seconds(spantree.in_window(spans, mine))
        templates[name] = {
            "executions": len(mine),
            "median_ms": statistics.median(1e3 * (e["end"] - e["start"]) for e in mine),
            "self_ms_per_execution": {
                n: 1e3 * s / len(mine) for n, s in sorted(own.items(), key=lambda kv: -kv[1])}}
    window = spantree.in_window(spans, runs)
    dispatches = spantree.join_dispatches(window)
    inside = [s for s in window
              if any(d[1] <= s[1] and s[2] <= d[2] for d in dispatches)]
    parts = spantree.self_seconds(inside, spantree.JOIN_PARTS)
    dispatch_s = sum(b - a for _n, a, b in dispatches)
    builds = [b - a for n, a, b in window if n == "residency.build"]
    return {
        "log_dir": log_dir, "spans": len(spans), "executions": len(runs),
        "templates": templates,
        "join_dispatch": {"dispatches": len(dispatches), "dispatch_s": dispatch_s, "share": {
            n: s / dispatch_s for n, s in parts.items()} if dispatch_s else {}},
        "residency_builds": {"count": len(builds), "seconds": sum(builds)},
        "clock_skew_us": clock_skew_us(
            [s for s in window if s[0] == skew_span], reduced["to_trace"],
            annotation_starts(tr.find_xplane(log_dir), skew_span))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("log_dir")
    ap.add_argument("--skew-span", default="device.dispatch")
    args = ap.parse_args(argv)
    print(json.dumps(report(args.log_dir, args.skew_span), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
