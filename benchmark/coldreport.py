"""Where a cell's set-up goes, for whoever writes `PERF.md` or aims a
`perf_opt` at `setup_s`.

    python3 benchmark/coldreport.py --workload <cell> --seed <n>

Does the cell's set-up as `run.py` does it (the device check, the tables from
the seed, `from_arrow(...).collect()`, each template twice), but with the
program's own `SpanRecorder` installed (it keeps a span's `args`, `id` and
`parent`) and `jax.profiler` started before the load, the `bench.sync`
annotation tying the two clocks as in `run.py`. No window follows. The last
line printed is one JSON object:

- `phases`: for the load and for each template's warm-up pass 1 and pass 2:
  `wall_s`; `self_ms`, the milliseconds each span name owns
  (`spantree.self_seconds`: a span's time less what was opened inside it;
  the owner of a moment is the open span that began last, so spans of pool
  threads share the wall time and the names add up to the time some span
  was open; a `residency.build` carries the slot kind that missed);
  `unnamed_s`, the seconds under no span but the `query` root or
  under none at all; `device_busy_s` (`xtrace.busy_seconds`; null where the
  profile has no device plane, as on the CPU); `counters`, the deltas of
  the cold counters in seconds (`setup_counters.COLD`: sums over threads,
  so they can exceed the wall; self times on a thread, so a calibration's
  counter lacks the programs its probes built); `calibrate_build_s`, the
  seconds of `xla.*` reports under a `placement.calibrate` span;
- `spans`, `dropped`: what the recorder held and what its `cap` refused;
- `generate_s`, `device`.

The benchmark's runs never call it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import setup_counters  # noqa: E402
import spantree  # noqa: E402
import xtrace as tr  # noqa: E402

SPAN_CAP = 1 << 20  # a whole set-up: the recorder's default 8,192 drops beyond it


def phase_report(phase, spans, xplane, to_trace):
    """One phase's entry of `phases` (see the module's docstring)."""
    name, t0, t1, before, after = phase
    mine = [s for s in spans if s["ts"] >= t0 and s["ts"] + s["dur"] <= t1]
    # a residency build by the slot kind that missed ("col", "dictcodes", "didx", ...)
    extents = [(f'{s["name"]}[{s["args"]["slot"]}]' if s["name"] == "residency.build"
                else s["name"], s["ts"], s["ts"] + s["dur"]) for s in mine]
    named = spantree.covered(extents, lambda n: n != spantree.ROOT)
    calibrations = {s["args"]["id"] for s in mine if s["name"] == "placement.calibrate"}
    try:
        busy = tr.busy_seconds(xplane, (t0 + to_trace, t1 + to_trace))
    except ValueError:  # no device plane: the CPU, or nothing ran on the device
        busy = None
    return {
        "phase": name, "wall_s": t1 - t0,
        "self_ms": {n: 1e3 * s for n, s in sorted(
            spantree.self_seconds(extents).items(), key=lambda kv: -kv[1])},
        "unnamed_s": (t1 - t0) - tr.length(tr.clip(named, t0, t1)),
        "device_busy_s": busy,
        "counters": {k: (after.get(k, 0) - before.get(k, 0)) / 1e6
                     for k in setup_counters.COLD if after.get(k, 0) != before.get(k, 0)},
        "calibrate_build_s": sum(
            s["dur"] for s in mine
            if s["name"].startswith("xla.") and s["args"]["parent"] in calibrations)}


def report(root: str, workload: str, seed: int, require_tpu: bool = True) -> dict:
    cell = run.Cell(root, workload)
    peaks = run.load_json(os.path.join(cell.bench_dir, "peaks.json"))
    device = run.find_device(cell.workload["chips"], peaks, require_tpu)

    import daft_tpu as dt
    import jax
    from daft_tpu.observability.runtime_stats import SpanRecorder, current_spans, set_spans
    from daft_tpu.ops import counters

    t0 = time.perf_counter()
    arrow = cell.datagen.generate(cell.config["scale_factor"], seed, cell.tables_read())
    generate_s = time.perf_counter() - t0

    log_dir = os.path.join(root, ".bench_trace", f"cold-{workload}-{seed}")
    rec, prev = SpanRecorder(cap=SPAN_CAP), current_spans()
    phases = []

    def timed(name, fn):
        before, start = counters.snapshot(), time.time()
        out = fn()
        phases.append((name, start, time.time(), before, counters.snapshot()))
        return out

    set_spans(rec)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as run.py traces: the host path as it is
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        sync_unix = time.time()
        with jax.profiler.TraceAnnotation(tr.SYNC_EVENT):
            pass
        tables = timed("load", lambda: {
            name: dt.from_arrow(t).collect() for name, t in arrow.items()})
        for name in cell.templates:
            program = cell.queries.TEMPLATES[name]["program"]
            for pass_no in (1, 2):
                timed(f"{name}.pass{pass_no}", lambda: run.execute(program, tables))
    finally:
        jax.profiler.stop_trace()
        set_spans(prev)
    spans = rec.drain()

    xplane = tr.read_xplane(tr.find_xplane(log_dir))
    if xplane["sync_s"] is None:
        raise run.HarnessError("the trace has no bench.sync event to tie the clocks by")
    to_trace = xplane["sync_s"] - sync_unix
    return {
        "workload": workload, "seed": seed, "device": device, "generate_s": generate_s,
        "spans": len(spans), "dropped": rec.dropped,
        "phases": [phase_report(p, spans, xplane, to_trace) for p in phases]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    try:
        run.refuse_program_knobs(os.environ)
        run.place_compile_cache(root, os.environ)
        sys.path.insert(0, root)
        result = report(root, args.workload, args.seed)
    except run.HarnessError as e:
        print(f"benchmark/coldreport.py: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
