"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process: it finds a TPU or fails naming the device it found, makes
the cell's tables from `--seed`, loads them through the program's public API
under the default `ExecutionConfig`, runs each of the cell's templates twice
as warm-up, measures a closed loop of one client for `--seconds`, checks what
the window produced against the plain reference, prints what it compared
beside each limit, and prints one JSON object as its last line.

`--trace 0` gives the cell's end-to-end metrics. `--trace 1` profiles a short
window instead (whole passes of the cycle until the traffic's `trace_seconds`
have gone by) with the program's spans on, and gives the per-layer metrics,
each from its own reader under `layer_metrics/`.

Everything that belongs to one cell is data or a file of its own, found by the
name `BENCHMARK.json` gives it; see README.md in this directory.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import arith  # noqa: E402
import compare as cmp  # noqa: E402
import xtrace as tr  # noqa: E402


class HarnessError(RuntimeError):
    """The run cannot be a measurement: no result line is printed."""


def say(**rec) -> None:
    print(json.dumps(rec, default=str), flush=True)


def load_module(path: str):
    """A file of the benchmark as a module, whatever its name has in it."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with the files it names, all found by name."""

    def __init__(self, root: str, workload: str):
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.spec["paths"][0])
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise HarnessError(f"no workload {workload!r}; there are {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        cfg = next(c for c in self.spec["configs"] if c["name"] == self.workload["config"])
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", self.workload["traffic"] + ".json"))
        suite = self.traffic["suite"]
        self.queries = load_module(os.path.join(self.bench_dir, "queries", suite + ".py"))
        self.reference = load_module(os.path.join(self.bench_dir, "reference", suite + ".py"))
        self.datagen = load_module(os.path.join(self.bench_dir, "datagen", suite + ".py"))
        self.templates = list(self.traffic["templates"])
        unknown = [t for t in self.templates if t not in self.queries.TEMPLATES]
        if unknown:
            raise HarnessError(f"traffic names templates the suite lacks: {unknown}")

    def metrics(self, kind: str):
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or self.name in m["workloads"]]

    def tables_read(self):
        seen = []
        for t in self.templates:
            for table in self.queries.TEMPLATES[t]["tables"]:
                if table not in seen:
                    seen.append(table)
        return seen


# ---- the device ------------------------------------------------------------------------

def refuse_program_knobs(environ) -> None:
    """The cells measure the shipped defaults: any DAFT_TPU_* variable would
    change what `auto` chooses or how the program runs."""
    knobs = sorted(k for k in environ if k.startswith("DAFT_TPU_"))
    if knobs:
        raise HarnessError(f"unset these before a benchmark run: {knobs}")


def place_compile_cache(root: str, environ) -> None:
    """Before JAX is imported. The cache stays where JAX_COMPILATION_CACHE_DIR
    says; without it, at one fixed path inside the checkout (the program's own
    default). Set-up only: every program is kept, however quick its compile,
    so that only a checkout's first run of a cell compiles."""
    environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache"))
    environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


def find_device(chips: int, peaks: dict, require_tpu: bool = True) -> dict:
    """The device as JAX reports it, and its row of the peaks table."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(phase="device", **device, jax=jax.__version__)
    if require_tpu:
        if device["platform"] != "tpu":
            raise HarnessError(f"no TPU: JAX found {device}")
        if device["count"] < chips:
            raise HarnessError(f"the cell needs {chips} chips: JAX found {device}")
        if device["kind"] not in peaks:
            raise HarnessError(
                f"device kind {device['kind']!r} is not in peaks.json "
                f"(it has {sorted(k for k in peaks if not k.startswith('_'))})")
    return device


class Compiles:
    """What JAX itself reports: seconds in XLA compilation (a persistent-cache
    hit costs its retrieval only), programs and cache hits. Copied from
    chip_smoke.py's `watch_compiles`."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return {"seconds": self.seconds, "programs": self.programs,
                "cache_hits": self.cache_hits}


class Spans:
    """The sink `profile_span` writes to (`runtime_stats.set_spans`): the
    program's spans with `time.time()` at both ends."""

    def __init__(self):
        self.spans = []

    def record(self, name, cat, t0, t1, args=None):
        self.spans.append((name, t0, t1))


# ---- one run ---------------------------------------------------------------------------

def execute(fn, tables):
    """One execution as a client sees it: the answer on the host."""
    return fn(tables).to_pydict()


def drive(cell: Cell, tables, order, seconds: float, per_execution_counters: bool):
    """The closed loop: one client, the templates of `order` in turn. A new
    pass starts while less than `seconds` have gone by, and a pass that has
    started is finished, so every run does whole passes: the same mix of work
    whatever the seed's order, and as many samples of each template. Returns
    (executions, answers): each execution carries the index of its answer
    among the distinct answers of its template."""
    from daft_tpu.ops import counters

    executions, answers = [], {t: [] for t in cell.templates}
    t0, i = time.perf_counter(), 0
    while True:
        if i % len(order) == 0 and time.perf_counter() - t0 >= seconds:
            break
        name = order[i % len(order)]
        i += 1
        before = counters.snapshot() if per_execution_counters else None
        rec = {"template": name, "failed": False, "answer": None,
               "start": time.perf_counter() - t0, "unix_start": time.time()}
        try:
            out = execute(cell.queries.TEMPLATES[name]["program"], tables)
        except Exception as e:  # the boundary: a raising execution is a failed one
            rec["failed"] = True
            say(phase="window", template=name, error=repr(e))
            out = None
        rec["end"] = time.perf_counter() - t0
        rec["unix_end"] = time.time()
        if before is not None:
            after = counters.snapshot()
            rec["counters"] = {k: after[k] - before.get(k, 0) for k in after
                               if after[k] != before.get(k, 0)}
        if out is not None:
            seen = answers[name]
            for k, a in enumerate(seen):
                if a == out:
                    rec["answer"] = k
                    break
            else:
                seen.append(out)
                rec["answer"] = len(seen) - 1
        executions.append(rec)
    return executions, answers


def check_answers(cell: Cell, arrow_tables, executions, answers):
    """Every distinct answer the window produced, and through them every
    execution, against the plain reference. Prints each number beside its
    limit; marks the executions whose answer is outside one as failed."""
    correct = True
    t0 = time.perf_counter()
    for name in cell.templates:
        ref = cell.reference.answer(name, arrow_tables)
        lim = cmp.limits(cell.config, name)
        verdicts = []
        for k, got in enumerate(answers[name]):
            numbers = cmp.compare(ref, got)
            ok = cmp.within(numbers, lim)
            verdicts.append(ok)
            say(phase="check", template=name, answer=k, ok=ok,
                compared={key: {"value": numbers[key], "limit": lim[key]} for key in lim},
                executions=sum(1 for e in executions
                               if e["template"] == name and e["answer"] == k))
        if not answers[name]:
            say(phase="check", template=name, ok=False, why="no answer in the window")
            correct = False
        for e in executions:
            if e["template"] == name and e["answer"] is not None \
                    and not verdicts[e["answer"]]:
                e["failed"] = True
    say(phase="check", reference_seconds=time.perf_counter() - t0)
    failed = sum(1 for e in executions if e["failed"])
    return correct and failed == 0, failed


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float = None) -> dict:
    """Everything after the arguments. Returns the result object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(root, workload)
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))
    device = find_device(cell.workload["chips"], peaks, require_tpu)
    compiles = Compiles()

    import daft_tpu as dt
    import jax
    from daft_tpu.observability.runtime_stats import current_spans, set_spans

    # the tables, from the seed, through the program's front door
    sf = cell.config["scale_factor"]
    t0 = time.perf_counter()
    arrow = cell.datagen.generate(sf, seed, cell.tables_read())
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    tables = {name: dt.from_arrow(t).collect() for name, t in arrow.items()}
    rows = {name: t.num_rows for name, t in arrow.items()}
    rows_read = {t: sum(rows[x] for x in cell.queries.TEMPLATES[t]["tables"])
                 for t in cell.templates}
    say(phase="load", scale_factor=sf, seed=seed, rows=rows,
        arrow_bytes=sum(t.nbytes for t in arrow.values()),
        generate_s=t_gen, collect_s=time.perf_counter() - t0)

    # warm-up: the cell's own templates, twice each; the second is the warm one
    for name in cell.templates:
        for pass_no in (1, 2):
            t0 = time.perf_counter()
            execute(cell.queries.TEMPLATES[name]["program"], tables)
            say(phase="warmup", template=name, pass_no=pass_no,
                ms=(time.perf_counter() - t0) * 1e3, compile=compiles.snapshot())

    order = list(cell.templates)
    random.Random(seed).shuffle(order)  # the same work from every seed, in another order
    setup_compiles = compiles.snapshot()
    big_arrays = [a for a in jax.live_arrays() if a.ndim and a.shape[0] >= max(rows.values())]

    log_dir, spans, prev_spans = None, None, None
    if trace:
        log_dir = os.path.join(root, ".bench_trace", f"{workload}-{seed}")
        spans, prev_spans = Spans(), current_spans()
        set_spans(spans)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host path is what is measured: keep it as it is
        jax.profiler.start_trace(log_dir, profiler_options=options)
        sync_unix = time.time()
        with jax.profiler.TraceAnnotation(tr.SYNC_EVENT):
            pass
    setup_s = time.perf_counter() - t_start
    window_unix = time.time()
    try:
        executions, answers = drive(
            cell, tables, order,
            seconds=min(seconds, cell.traffic["trace_seconds"]) if trace else seconds,
            per_execution_counters=trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
            set_spans(prev_spans)
    window_s = max(e["end"] for e in executions)
    window_compiles = compiles.programs - setup_compiles["programs"]
    stats = jax.devices()[0].memory_stats() or {}
    say(phase="window", executions=len(executions), window_s=window_s,
        window_compiles=window_compiles, order=order)

    del tables  # the reference works on the Arrow tables, after the window
    correct, failed = check_answers(cell, arrow, executions, answers)

    result = {"correct": correct, "attempted": len(executions), "failed": failed,
              "metrics": {}, "device": dict(device)}
    result["device"]["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    if not trace:
        wanted = cell.metrics("end_to_end")
        values = end_to_end_values(cell, executions, rows_read, setup_s)
        if len(values) == 1:  # a template never completed: not correct, no time to report
            result["correct"] = False
    else:
        wanted = cell.metrics("per_layer")
        values = per_layer_values(
            cell, wanted, result, log_dir, executions, spans.spans,
            sync_unix=sync_unix, window_unix=window_unix, window_s=window_s,
            setup_compiles=setup_compiles, window_compiles=window_compiles,
            peaks=peaks.get(device["kind"]), rows=rows,
            big_arrays=[(a.shape, str(a.dtype), a.nbytes) for a in big_arrays])
    for m in wanted:
        if values.get(m["name"]) is not None:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return result


def end_to_end_values(cell: Cell, executions, rows_read, setup_s: float) -> dict:
    """The `--trace 0` metrics by name; `setup_s` alone where some template
    never completed."""
    values = {"setup_s": setup_s}
    by = arith.durations_ms(executions)
    say(phase="metrics", samples={t: len(v) for t, v in by.items()},
        median_ms={t: statistics.median(v) for t, v in by.items()})
    if all(t in by for t in cell.templates):
        p95, n = arith.query_ms_p95(executions)
        say(phase="metrics", query_ms_p95=p95, samples=n)
        values.update({
            "query_ms.geomean": arith.query_ms_geomean(executions, cell.templates),
            "query_ms.p95": p95,
            "scan_rows_per_s": arith.scan_rows_per_s(executions, rows_read)})
    return values


def per_layer_values(cell: Cell, wanted, result: dict, log_dir: str, executions, spans,
                     *, sync_unix, window_unix, window_s, **for_readers) -> dict:
    """The `--trace 1` metrics by name, each from its own reader; puts
    `busy_s`, `window_s` and `breakdown` into `result` on the way."""
    xplane = tr.read_xplane(tr.find_xplane(log_dir))
    if xplane["sync_s"] is None:
        raise HarnessError("the trace has no bench.sync event to tie the clocks by")
    to_trace = xplane["sync_s"] - sync_unix  # unix seconds -> the trace's clock
    window = (window_unix + to_trace, window_unix + to_trace + window_s)
    busy_s = tr.busy_seconds(xplane, window)
    result["device"].update(busy_s=busy_s, window_s=window_s)
    busy = tr.busy_union(xplane)
    idle = tr.attribute_gaps(tr.gaps(busy, window),
                             [(n, a + to_trace, b + to_trace) for n, a, b in spans])
    result["breakdown"] = {
        # the trace names an operation by its whole HLO text: the head of it is enough
        "device_ops": [[n[:100], s] for n, s in tr.top_ops(xplane, window)],
        "idle_gaps": sorted(([n, s] for n, s in idle.items()), key=lambda x: -x[1])[:10]}
    with open(os.path.join(log_dir, "reduced.json"), "w") as f:
        # beside the trace, for whoever reads it by hand (git-ignored)
        json.dump({"window": window, "to_trace": to_trace, "spans": spans,
                   "executions": executions, "busy_s": busy_s}, f)
    ctx = {"cell": cell.name, "templates": cell.templates, "executions": executions,
           "spans": spans, "trace": xplane, "busy": busy, "window": window,
           "to_trace": to_trace, "window_s": window_s,
           "queries": cell.queries.TEMPLATES, **for_readers}
    values = {}
    for m in wanted:
        reader = load_module(os.path.join(cell.bench_dir, "layer_metrics", m["name"] + ".py"))
        values[m["name"]] = reader.read(ctx)  # None: nothing to read in this run
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    try:
        refuse_program_knobs(os.environ)
        place_compile_cache(root, os.environ)
        sys.path.insert(0, root)
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=_T_START)
    except HarnessError as e:
        # no result line: a run that cannot be a measurement prints none
        print(f"benchmark/run.py: FAILED: {e}", flush=True)
        print(f"benchmark/run.py: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
