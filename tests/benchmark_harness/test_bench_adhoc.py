"""The `tpch_adhoc` suite (`tpch_sf10.adhoc_scanagg`): the run's draws are
distinct, inside the specification's substitution domains and a function of
the seed; the cell's traffic is correct through the harness at a test's size;
the bfloat16 control is not; the suite refuses a program without the counter
of traced stage programs and one that traces a program for a value; the
`literals.*` readers on a hand-made window; and the entries stand at the end
of their lists, after the four-chip cell's, which are as they were. On the
CPU: nothing here is a measurement."""

import datetime
import os

import pytest

import adhoc_params
import run
from bench_helpers import BENCH, REPO, add_cell

CELL = "tpch_sf10.adhoc_scanagg"
CONFIG = "tpch-sf10-adhoc-1chip"
# what reads a counter or a span that came with the literal arguments, then
# the cell's twins of accepted readers whose lists cannot take the cell
PROGRAM_METRICS = ["literals.stage_program_traces", "literals.args_per_dispatch",
                   "literals.bind_ms", "literals.launch_ms", "literals.scan_hbm_share"]
TWINS = {"literals.launch_ms": "stages.launch_ms",
         "literals.scan_hbm_share": "kernels.scan_hbm_share",
         "literals.dispatch_host_ms": "stages.dispatch_host_ms",
         "literals.finalize_ms": "stages.finalize_ms",
         "literals.plan_ms": "plan.plan_ms",
         "literals.decide_ms": "placement.decide_ms",
         "literals.host_ops_ms": "host.ops_ms",
         "literals.residency_misses": "residency.misses_per_query",
         "literals.idle_unattributed_share": "idle.unattributed_share"}
LITERAL_METRICS = PROGRAM_METRICS + list(TWINS)[2:] + ["literals.query_p95_ms"]
TEMPLATES = [f"{q}.p{i:02d}" for q in ("q1", "q6") for i in range(12)]


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def suite(kind):
    return run.load_module(os.path.join(BENCH, kind, "tpch_adhoc.py"))


# ---- the entries and the configuration ---------------------------------------------------

def test_the_entries_stand_at_the_end_of_their_lists():
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert [w["name"] for w in spec["workloads"]][-2:] == ["tpch_sf30_mesh4.scanagg", CELL]
    assert [c["name"] for c in spec["configs"]][-2:] == ["tpch-sf30-4chip", CONFIG]
    assert spec["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "scanagg_adhoc", "chips": 1,
        "why": spec["workloads"][-1]["why"]}
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-13:] == LITERAL_METRICS and all(n.startswith("mesh.") for n in names[-18:-13])
    mine = [m for m in spec["per_layer"] if m["name"].startswith("literals.")]
    assert [m["name"] for m in mine] == LITERAL_METRICS
    assert all(m["workloads"] == [CELL] for m in mine)
    assert [m["layer"] for m in mine[:5]] == ["Device stages"] * 4 + ["Kernels"]
    assert [m["moves"] for m in mine[:5]] == ["setup_s"] + ["query_ms.geomean"] * 4
    # a twin says of its reading what the accepted metric says of it
    entries = {m["name"]: m for m in spec["per_layer"]}
    for twin, accepted in TWINS.items():
        assert {k: entries[twin][k] for k in ("unit", "better", "source", "layer", "moves")} \
            == {k: entries[accepted][k] for k in ("unit", "better", "source", "layer", "moves")}
        assert CELL not in entries[accepted].get("workloads", [CELL])
    cell = run.Cell(REPO, CELL)
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert reported == set(LITERAL_METRICS) | {
        "placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
        "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"}
    # no list that was there took the cell
    assert all(CELL not in m.get("workloads", []) for m in spec["per_layer"] + spec["end_to_end"]
               if not m["name"].startswith("literals."))


def test_the_four_chip_cells_entries_are_as_they_were():
    """What `test_bench_mesh.py`'s entries test holds of the four-chip cell, but
    for one thing: it pins the cell's entries to the END of their lists, and a
    PR may add entries nowhere else, so since this PR they are the last ones
    before this PR's (`tests/conftest.py` marks that test as expected to fail)."""
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    mesh_cell = "tpch_sf30_mesh4.scanagg"
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [mesh_cell]
    assert four[0]["config"] == "tpch-sf30-4chip" and four[0]["traffic"] == "scanagg_mesh"
    assert spec["workloads"][-2] == four[0] and spec["configs"][-2]["name"] == "tpch-sf30-4chip"
    cell = run.Cell(REPO, mesh_cell)
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    mesh = [m for m in spec["per_layer"] if m["name"].startswith("mesh.")]
    assert [m["name"] for m in mesh] == [m["name"] for m in spec["per_layer"][-18:-13]] == [
        "mesh.shards_per_dispatch", "mesh.launch_ms", "mesh.shard_skew_share",
        "mesh.collective_share", "mesh.scan_hbm_share"]
    assert all(m["workloads"] == [mesh_cell] and m["layer"] == "Mesh" for m in mesh)
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert reported == {m["name"] for m in mesh} | {
        "placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
        "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"}


def test_the_configuration_states_the_deployment():
    cell = run.Cell(REPO, CELL)
    cfg, scan = cell.config, run.load_json(os.path.join(BENCH, "configs", "tpch-sf10-1chip.json"))
    assert cfg["name"] == CONFIG and cfg["suite"] == "tpch_adhoc"
    assert cfg["scale_factor"] == scan["scale_factor"] == 10 and cfg["chips"] == 1
    assert cfg["source_scale_factor"] == 100 and list(cfg["reduced"]) == ["scale_factor"]
    assert cfg["source"] != scan["source"] and "2.4.1.3" in cfg["source"] \
        and "2.4.6.3" in cfg["source"] and len(cfg["source"]) <= 200
    # the scan cell's guarantees and one more
    assert set(cfg["guarantees"]) == set(scan["guarantees"]) | {"parameters"}
    assert cfg["guarantees"]["exact"] == scan["guarantees"]["exact"]
    assert cfg["guarantees"]["floats"] == scan["guarantees"]["floats"]
    assert cfg["assumed"][:2] == scan["assumed"] and len(cfg["assumed"]) == 5
    assert list(cfg["float_rel_limit"]) == TEMPLATES == cell.templates
    # no looser than the scan cell's limits, and one limit a query
    for t in TEMPLATES:
        assert cfg["float_rel_limit"][t] <= scan["float_rel_limit"][t[:2]]
        assert cfg["float_rel_limit"][t] == cfg["float_rel_limit"][t[:2] + ".p00"]
    traffic = run.load_json(os.path.join(BENCH, "traffic", "scanagg_adhoc.json"))
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["trace_seconds"] == 3 and traffic["suite"] == "tpch_adhoc"
    queries = suite("queries")
    assert list(queries.TEMPLATES) == TEMPLATES == list(adhoc_params.template_names())
    plain = run.load_module(os.path.join(BENCH, "queries", "tpch.py"))
    for name, t in queries.TEMPLATES.items():
        assert {k: v for k, v in t.items() if k != "program"} \
            == {k: v for k, v in plain.TEMPLATES[name[:2]].items() if k != "program"}


# ---- the draws -----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3, 2340000017])
def test_the_draws_are_distinct_inside_the_domains_and_a_function_of_the_seed(seed):
    q1, q6 = adhoc_params.draws(seed)
    assert (q1, q6) == adhoc_params.draws(seed) != adhoc_params.draws(seed + 1)
    assert len(q1) == len(set(q1)) == len(q6) == len(set(q6)) == adhoc_params.DRAWS == 12
    assert all(60 <= p.delta <= 120 for p in q1)
    assert all(datetime.date(1998, 8, 3) <= p.cutoff <= datetime.date(1998, 10, 2) for p in q1)
    for p in q6:
        assert 1993 <= p.year <= 1997 and p.quantity in (24, 25)
        assert p.discount in (0.02, 0.03, 0.04, 0.05, 0.06, 0.07, 0.08, 0.09)
        assert (p.start, p.end) == (datetime.date(p.year, 1, 1), datetime.date(p.year + 1, 1, 1))
        # the bounds are the decimals of the specification, not float sums
        assert p.low in (0.01, 0.02, 0.03, 0.04, 0.05, 0.06, 0.07, 0.08)
        assert p.high in (0.03, 0.04, 0.05, 0.06, 0.07, 0.08, 0.09, 0.1)
        assert round(100 * (p.high - p.low)) == 2
    assert (len(adhoc_params.Q1_DELTAS),
            len(adhoc_params.Q6_YEARS) * len(adhoc_params.Q6_DISCOUNTS)
            * len(adhoc_params.Q6_QUANTITIES)) == (61, 80)


def test_the_generator_makes_the_runs_draws_and_the_scan_cells_tables():
    arrow = suite("datagen").generate(0.002, 11, ["lineitem"])
    assert adhoc_params.of("q1.p03") == adhoc_params.draws(11)[0][3]
    assert adhoc_params.of("q6.p11") == adhoc_params.draws(11)[1][11]
    same = run.load_module(os.path.join(BENCH, "datagen", "tpch.py")).generate(
        0.002, 11, ["lineitem"])
    assert arrow["lineitem"].equals(same["lineitem"])
    suite("datagen").generate(0.002, 12, ["lineitem"])
    assert adhoc_params.of("q1.p03") == adhoc_params.draws(12)[0][3]


def test_the_validation_values_give_the_scan_cells_answers():
    """The templates are `queries/tpch.py`'s texts: with the specification's
    validation values (DELTA 90; 1994, 0.06, 24) they give its answers, and
    the reference gives `reference/tpch.py`'s."""
    import daft_tpu as dt

    arrow = suite("datagen").generate(0.01, 5, ["lineitem"])
    tables = {"lineitem": dt.from_arrow(arrow["lineitem"]).collect()}
    queries, ref = suite("queries"), suite("reference")
    plain = run.load_module(os.path.join(BENCH, "queries", "tpch.py"))
    plain_ref = run.load_module(os.path.join(BENCH, "reference", "tpch.py"))
    values = {"q1": adhoc_params.Q1(90), "q6": adhoc_params.Q6(1994, 0.06, 24)}
    assert values["q1"].cutoff == datetime.date(1998, 9, 2)
    assert (values["q6"].low, values["q6"].high) == (0.05, 0.07)
    for name, p in values.items():
        got = getattr(queries, name)(tables, p).to_pydict()
        assert got == plain.TEMPLATES[name]["program"](tables).to_pydict()
        assert ref.answer_for(name, p, arrow) == plain_ref.answer(name, arrow)


# ---- through the harness ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2**31 + 3, 7])
def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root, seed):
    add_cell(bench_root, "tiny.scanagg_adhoc", "tiny", "scanagg_adhoc", scale_factor=0.05,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    result = run.run_cell(bench_root, "tiny.scanagg_adhoc", seed=seed, seconds=0.2,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 24
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}
    assert result["metrics"]["scan_rows_per_s"]["value"] > 0
    assert adhoc_params.of("q1.p00") == adhoc_params.draws(seed)[0][0]


def test_every_template_has_an_answer_of_its_own():
    """Twelve draws a query are twelve answers: a program (or a reference)
    that answered a template with another's values would be caught."""
    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, 3, cell.tables_read())
    answers = {t: cell.reference.answer(t, arrow) for t in cell.templates}
    for q in ("q1", "q6"):
        mine = [repr(answers[t]) for t in cell.templates if t.startswith(q)]
        assert len(set(mine)) == 12


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """The reference with its float columns stored in bfloat16, put in the
    program's place, fails the limits of the cell's own configuration (at a
    test's scale; the readings at SF10 are in the configuration's file and
    PERF.md section 2)."""
    import compare

    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = {}
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts[name] = compare.within(compare.compare(ref, low), lim)
    assert not any(verdicts[t] for t in cell.templates if t.startswith("q6")), verdicts


# ---- the suite's own check -------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_tables():
    import daft_tpu as dt

    arrow = suite("datagen").generate(0.002, 6, ["lineitem"])
    return {"lineitem": dt.from_arrow(arrow["lineitem"]).collect()}


def test_a_program_without_the_counter_ends_the_run_at_import(monkeypatch, capsys):
    """The parent of the PR that added the cell: the suite exits 1 as it is
    imported, before any data is made, naming what is missing."""
    from daft_tpu.observability import metrics

    suite("queries")  # this program declares it
    monkeypatch.setattr(metrics, "DEVICE_COUNTER_NAMES", tuple(
        c for c in metrics.DEVICE_COUNTER_NAMES if c != "device_stage_program_traces"))
    with pytest.raises(SystemExit) as e:
        suite("queries")
    assert e.value.code == 1
    out = capsys.readouterr()
    assert "device_stage_program_traces" in out.out and "device_stage_program_traces" in out.err
    with pytest.raises(SystemExit):
        run.Cell(REPO, CELL)  # the harness finds the cell's files first of all


@pytest.mark.parametrize("backend, counts, ends", [
    # (traces at a template's first build, at its second), in the harness's
    # warm-up order: q1.p00 twice, q1.p01 twice, q6.p00 twice, q6.p01 twice
    ("cpu", [(0, 1), (1, 2), (2, 3), (3, 4)], None),       # tier-1 tests: nothing is checked
    ("tpu", [(0, 1), (1, 1), (1, 2), (2, 2)], None),       # a program a query
    ("tpu", [(5, 7), (7, 7), (7, 8), (8, 8)], None),       # a bucket or a width more, once
    ("tpu", [(0, 1), (1, 2), (2, 3), (3, 4)], "q1.p01"),   # a program a value
    ("tpu", [(0, 1), (1, 1), (1, 2), (2, 3)], "q6.p01"),   # q6's alone
])
def test_a_value_must_not_trace_a_program(monkeypatch, capsys, tiny_tables,
                                          backend, counts, ends):
    import jax

    queries = suite("queries")  # a fresh module: a fresh count of builds
    suite("datagen").generate(0.002, 6, ["lineitem"])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    it = iter([c for pair in counts for c in pair])
    monkeypatch.setattr(queries, "_traces", lambda: next(it))
    for name in ("q1.p00", "q1.p01", "q6.p00", "q6.p01"):
        program = queries.TEMPLATES[name]["program"]
        built = program(tiny_tables)  # built, never executed
        assert len(built.schema.column_names()) in (10, 1)
        if name == ends:
            with pytest.raises(SystemExit) as e:
                program(tiny_tables)
            assert e.value.code == 1
            out = capsys.readouterr()
            assert name in out.out and "device_stage_program_traces" in out.err \
                and "literal values" in out.err
            return
        program(tiny_tables)
    assert ends is None
    # a third build checks nothing and reads no counter
    queries.TEMPLATES["q1.p01"]["program"](tiny_tables)


def test_a_program_that_compiles_a_value_ends_the_run_in_warm_up(bench_root, monkeypatch,
                                                                 capsys):
    """Through the harness: on a TPU backend a program whose every new value
    traces a stage program ends the run in warm-up, before the window."""
    import jax
    from daft_tpu.ops import counters

    add_cell(bench_root, "tiny.scanagg_adhoc", "tiny", "scanagg_adhoc", scale_factor=0.01,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    real, calls = counters.snapshot, [0]

    def snapshot():
        # as if every execution so far had traced a program of its own
        calls[0] += 1
        return dict(real(), device_stage_program_traces=calls[0])

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(counters, "snapshot", snapshot)
    with pytest.raises(SystemExit) as e:
        run.run_cell(bench_root, "tiny.scanagg_adhoc", seed=5, seconds=0.2,
                     trace=False, require_tpu=False)
    assert e.value.code == 1
    out = capsys.readouterr().out
    assert "q1.p01's first execution" in out and '"phase": "window"' not in out


# ---- the readers -----------------------------------------------------------------------

# two executions: a q1 (0..10 s) and a q6 (10..20 s), one dispatch each
TRACE = {"sync_s": 0.0, "device": {"/device:TPU:0": {"XLA Ops": [
    ("while.5", 1.0, 4.0, ), ("fusion.3", 11.0, 2.0)]}}}
RUNS = [
    {"template": "q1.p04", "unix_start": 0.0, "unix_end": 10.0, "start": 0.0, "end": 10.0,
     "failed": False, "counters": {"device_grouped_batches": 1, "device_literal_args": 4}},
    {"template": "q6.p09", "unix_start": 10.0, "unix_end": 20.0, "start": 10.0, "end": 20.0,
     "failed": False, "counters": {"device_stage_batches": 1, "device_literal_args": 5}},
]
SPANS = [("query", 0.0, 10.0), ("device.dispatch", 0.5, 1.0), ("device.literals", 0.5, 0.52),
         ("device.launch", 0.6, 0.9),
         ("query", 10.0, 20.0), ("device.dispatch", 10.5, 11.0),
         ("device.literals", 10.5, 10.51), ("device.launch", 10.6, 10.7),
         ("device.literals", 30.0, 31.0)]   # outside every execution
PLANE_BYTES = 4 * (1 << 20)


def ctx_of(runs=RUNS, spans=SPANS, hbm_bytes_per_s=1e7):
    import xtrace

    return {"trace": TRACE, "executions": list(runs), "spans": list(spans), "to_trace": 0.0,
            "window": (0.0, 20.0), "queries": suite("queries").TEMPLATES,
            "busy": xtrace.busy_union(TRACE),
            "big_arrays": [((1 << 20,), "float32", PLANE_BYTES)] * 7
            + [((1 << 20,), "bool", 1 << 20)] * 7,
            "peaks": {"hbm_bytes_per_s": hbm_bytes_per_s, "f32_flops_per_s": 1e12}}


@pytest.mark.parametrize("name, want", [
    ("literals.args_per_dispatch", 4.5),
    ("literals.bind_ms", 1e3 * (0.02 + 0.01) / 2),
    ("literals.launch_ms", 1e3 * (0.3 + 0.1) / 2),
    # q1 reads 7 planes, q6 4: 11 planes' bytes against 4 + 2 busy seconds
    ("literals.scan_hbm_share", 100.0 * (11 * PLANE_BYTES / 1e7) / 6.0),
])
def test_the_literal_readers_on_a_hand_made_window(name, want):
    assert reader(name).read(ctx_of()) == pytest.approx(want)


def test_the_trace_count_is_the_whole_processs():
    from daft_tpu.ops import counters

    assert reader("literals.stage_program_traces").read(ctx_of()) \
        == counters.snapshot()["device_stage_program_traces"]


def test_the_roofline_share_cannot_pass_100_percent():
    """A chip cannot read the planes faster than its HBM gives them: busy
    exactly the least time, the share is 100%; miscounted bytes would show."""
    least = 11 * PLANE_BYTES / 1e7
    full = dict(ctx_of(), busy=[(1.0, 1.0 + 7 * PLANE_BYTES / 1e7),
                                (11.0, 11.0 + 4 * PLANE_BYTES / 1e7)])
    assert reader("literals.scan_hbm_share").read(full) == pytest.approx(100.0)
    assert reader("literals.scan_hbm_share").read(ctx_of()) < 100.0 * least / 5.9
    failed = [dict(RUNS[0], failed=True), RUNS[1]]
    assert reader("literals.scan_hbm_share").read(ctx_of(failed)) \
        == pytest.approx(100.0 * (4 * PLANE_BYTES / 1e7) / 2.0)


# spans and a counter the accepted readers read, inside the two executions
TREE = [("plan.optimize", 0.1, 0.2), ("plan.translate", 0.2, 0.25),
        ("op.DeviceGroupedAgg", 0.3, 1.6), ("placement.decide", 0.3, 0.45),
        ("stage.finalize", 1.0, 1.5), ("device.d2h", 1.1, 1.3),
        ("plan.translate", 10.2, 10.21), ("op.DeviceFilterAgg", 10.3, 11.4),
        ("stage.finalize", 11.0, 11.3), ("device.d2h", 11.05, 11.25)]


@pytest.mark.parametrize("twin, accepted", sorted(TWINS.items()))
def test_a_twin_reads_what_the_accepted_reader_reads(twin, accepted):
    """One arithmetic under two names (`benchmark/twin.py`): on a window with
    every span the accepted readers look for, the twin gives their number."""
    runs = [dict(r, counters=dict(r["counters"], hbm_cache_misses=k))
            for k, r in enumerate(RUNS)]
    ctx = ctx_of(runs, SPANS + TREE)
    got = reader(twin).read(ctx)
    assert got is not None and got == reader(accepted).read(ctx)


def test_the_p95_of_the_traced_window():
    runs = [dict(RUNS[0], start=0.0, end=0.010 + 0.001 * k) for k in range(40)] \
        + [dict(RUNS[1], failed=True, start=0.0, end=9.0)]
    got = reader("literals.query_p95_ms").read(ctx_of(runs))
    assert 47.0 <= got <= 49.0   # of 10..49 ms; the failed execution is left out
    assert reader("literals.query_p95_ms").read(ctx_of([dict(RUNS[0], failed=True)])) is None


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_a_program_without_the_counters_and_the_span_gives_nothing_to_read(name, monkeypatch):
    """The parent's program: no `device_stage_program_traces`, no
    `device_literal_args`, no `device.literals` span, and (a host-tier window)
    no launch and no device time: None, not a raise, so the result line
    leaves the metric out."""
    from daft_tpu.ops import counters

    real = counters.snapshot
    monkeypatch.setattr(counters, "snapshot", lambda: {
        k: v for k, v in real().items()
        if k not in ("device_stage_program_traces", "device_literal_args")})
    bare = [dict(r, counters={}) for r in RUNS]
    ctx = dict(ctx_of(bare, [("query", 0.0, 10.0), ("query", 10.0, 20.0)]), busy=[])
    assert reader(name).read(ctx) is None


def test_the_reference_keeps_its_columns_between_calls_and_drops_them_with_the_table():
    """24 answers and the control's 24 read `lineitem` once each: one set of
    columns a `storage`, both kept while the table is the same."""
    ref = suite("reference")
    arrow = suite("datagen").generate(0.002, 9, ["lineitem"])
    ref.answer("q1.p00", arrow)
    plain = ref._columns[None]
    ref.answer("q6.p03", arrow, ref.to_bfloat16)
    ref.answer("q1.p05", arrow)
    assert ref._columns[None] is plain and set(ref._columns) == {None, ref.to_bfloat16}
    assert ("num", "l_quantity") in plain._kept and ("codes", "l_returnflag") in plain._kept
    other = suite("datagen").generate(0.002, 10, ["lineitem"])
    ref.answer("q6.p00", other)
    assert set(ref._columns) == {None} and ref._columns[None].table is other["lineitem"]
