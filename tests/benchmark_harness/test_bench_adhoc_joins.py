"""The `tpch_adhoc_joins` suite (`tpch_sf10.adhoc_joins`): the run's draws are
distinct, inside the specification's substitution domains and a function of
the seed; the templates are the join cell's own texts, and with the
validation values give its answers; the cell's traffic is correct through
the harness at a test's size, and a template given another template's values
is not; the bfloat16 control is not correct; on the device tier the twelve
templates give the reference's and the host tier's answers and a new value
rebuilds no slot and traces no program; the suite refuses a program without
the counters and one that pays a rebuild or a trace for a value; the
`adhocjoin.*` readers on a hand-made window (a roofline share that cannot
pass 100%), each None where there is nothing to read; and the entries, held
BY NAME, with what `test_bench_joins_mesh.py` held of the one-chip join cell
by position (`tests/conftest.py`). On the CPU: nothing here is a measurement."""

import datetime
import json
import os

import pytest

import adhoc_join_params
import compare
import run
from bench_helpers import BENCH, REPO, add_cell

CELL = "tpch_sf10.adhoc_joins"
CONFIG = "tpch-sf10-adhoc-joins-1chip"
PROGRAM_METRICS = ["adhocjoin.literal_rebuilds_per_query", "adhocjoin.filter_program_traces",
                   "adhocjoin.filter_args_per_query", "adhocjoin.filter_ms",
                   "adhocjoin.filter_hbm_share"]
TWINS = {"adhocjoin.join_hbm_share": "jointopn.join_hbm_share",
         "adhocjoin.dispatch_host_ms": "stages.dispatch_host_ms",
         "adhocjoin.launch_ms": "stages.launch_ms",
         "adhocjoin.gather_ms": "join.gather_ms",
         "adhocjoin.select_ms": "jointopn.select_ms",
         "adhocjoin.batches_per_query": "jointopn.batches_per_query",
         "adhocjoin.residency_misses": "residency.misses_per_query",
         "adhocjoin.decide_ms": "placement.decide_ms"}
METRICS = PROGRAM_METRICS + list(TWINS)
LIST_LESS = {"placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
             "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"}
TEMPLATES = [f"{q}.p{i:02d}" for q in ("q3", "q5", "q10") for i in range(4)]
NEW_COUNTERS = ("hbm_literal_rebuilds", "join_filter_program_traces", "join_filter_literal_args")
TABLES = ["region", "nation", "customer", "orders", "lineitem", "supplier"]


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def suite(kind):
    return run.load_module(os.path.join(BENCH, kind, "tpch_adhoc_joins.py"))


# ---- the entries and the configuration ---------------------------------------------------

def test_the_cell_and_its_metrics_are_entries_by_name():
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    assert by_name[CELL] == dict(by_name[CELL], config=CONFIG, traffic="joins_adhoc", chips=1)
    assert len(by_name[CELL]["why"]) <= 200
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["scale_factor"] and len(config["source"]) <= 200
    # one use of a configuration's file, and of a (configuration, traffic) pair
    assert [c["file"] for c in spec["configs"]].count(config["file"]) == 1
    assert [(w["config"], w["traffic"]) for w in spec["workloads"]].count(
        (CONFIG, "joins_adhoc")) == 1
    cell = run.Cell(REPO, CELL)
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    mine = [m for m in spec["per_layer"] if m["name"].startswith("adhocjoin.")]
    assert [m["name"] for m in mine] == METRICS
    assert all(m["workloads"] == [CELL] for m in mine)
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    assert [(per_layer[n]["layer"], per_layer[n]["moves"], per_layer[n]["unit"])
            for n in PROGRAM_METRICS] == [
        ("h2d, residency", "query_ms.geomean", "count"), ("Compile", "setup_s", "count"),
        ("Device stages", "query_ms.geomean", "count"),
        ("Device stages", "query_ms.geomean", "ms"), ("Kernels", "query_ms.geomean", "%")]
    for new, old in TWINS.items():   # a twin says of its reading what the accepted metric says
        assert {k: per_layer[new][k] for k in ("unit", "better", "source", "layer", "moves")} \
            == {k: per_layer[old][k] for k in ("unit", "better", "source", "layer", "moves")}
        assert CELL not in per_layer[old].get("workloads", [CELL])
    assert {m["name"] for m in cell.metrics("per_layer")} == set(METRICS) | LIST_LESS
    # no list that was there took the cell
    assert all(CELL not in m.get("workloads", []) for m in spec["per_layer"] + spec["end_to_end"]
               if not m["name"].startswith("adhocjoin."))
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    # eight cells, two of them on four chips (four may ask for them)
    assert len(spec["workloads"]) == 8
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] \
        == ["tpch_sf30_mesh4.scanagg", "tpch_sf30_mesh4.joins"]


def test_the_join_cells_entries_are_as_they_were():
    """What `test_bench_joins_mesh.py::test_the_one_chip_join_cells_entries_
    are_as_they_were` holds besides the END of the lists (PR 41's entries
    stood there until this cell's followed them: tests/conftest.py), kept by
    name, with the four-chip join cell's own."""
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    one = next(w for w in spec["workloads"] if w["name"] == "tpch_sf10.joins")
    assert (one["config"], one["traffic"], one["chips"]) \
        == ("tpch-sf10-joins-1chip", "joins_sf10", 1)
    cell = run.Cell(REPO, "tpch_sf10.joins")
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    theirs = [m for m in spec["per_layer"] if m["name"].startswith("jointopn.")]
    assert len(theirs) == 11 and all(m["workloads"] == ["tpch_sf10.joins"] for m in theirs)
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert not {m for m in reported if m.startswith(("meshjoin.", "mesh.", "adhocjoin."))}
    # the accepted entries stand where they stood, in their order: PR 41's
    # follow PR 38's, this PR's follow PR 41's
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index("jointopn.join_hbm_share") + 1 == names.index("meshjoin.shards_per_dispatch")
    assert names.index("meshjoin.join_hbm_share") + 1 == names.index(METRICS[0])
    assert names[-len(METRICS):] == METRICS
    cells = [w["name"] for w in spec["workloads"]]
    assert cells[cells.index("tpch_sf10.joins"):] \
        == ["tpch_sf10.joins", "tpch_sf30_mesh4.joins", CELL]
    configs = [c["name"] for c in spec["configs"]]
    assert configs[configs.index("tpch-sf10-joins-1chip"):] \
        == ["tpch-sf10-joins-1chip", "tpch-sf30-joins-4chip", CONFIG]
    mesh = next(w for w in spec["workloads"] if w["name"] == "tpch_sf30_mesh4.joins")
    assert (mesh["config"], mesh["traffic"], mesh["chips"]) \
        == ("tpch-sf30-joins-4chip", "joins_mesh", 4)
    assert all(m["workloads"] == ["tpch_sf30_mesh4.joins"] for m in spec["per_layer"]
               if m["name"].startswith("meshjoin."))


def test_the_configuration_states_the_deployment():
    cell = run.Cell(REPO, CELL)
    cfg = cell.config
    one = run.load_json(os.path.join(BENCH, "configs", "tpch-sf10-joins-1chip.json"))
    adhoc = run.load_json(os.path.join(BENCH, "configs", "tpch-sf10-adhoc-1chip.json"))
    assert cfg["name"] == CONFIG and cfg["suite"] == "tpch_adhoc_joins"
    assert cfg["scale_factor"] == one["scale_factor"] == 10 and cfg["chips"] == 1
    assert cfg["source_scale_factor"] == 100 and list(cfg["reduced"]) == ["scale_factor"]
    assert cfg["reduced"] == one["reduced"]
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert cfg["source"] == next(c for c in spec["configs"] if c["name"] == CONFIG)["source"]
    assert cfg["source"] not in (one["source"], adhoc["source"]) and len(cfg["source"]) <= 200
    for clause in ("2.4.3.3", "2.4.5.3", "2.4.10.3", "SEGMENT", "REGION", "SF10"):
        assert clause in cfg["source"]
    # tpch-sf10-joins-1chip's three guarantees word for word but for the
    # reference's file, and tpch-sf10-adhoc-1chip's fourth but for the draws' file
    assert set(cfg["guarantees"]) == set(one["guarantees"]) | {"parameters"}
    assert cfg["guarantees"]["exact"] == one["guarantees"]["exact"]
    assert cfg["guarantees"]["floats"] == one["guarantees"]["floats"]
    assert cfg["guarantees"]["answers"] == one["guarantees"]["answers"].replace(
        "reference/tpch_joins10.py", "reference/tpch_adhoc_joins.py")
    assert cfg["guarantees"]["parameters"] == adhoc["guarantees"]["parameters"].replace(
        "adhoc_params.py", "adhoc_join_params.py")
    assert cfg["assumed"][0] == one["assumed"][0] and len(cfg["assumed"]) == 5
    assert "qgen" in cfg["assumed"][2] and "5.3.4" in cfg["assumed"][3]
    assert list(cfg["float_rel_limit"]) == TEMPLATES == cell.templates
    for t in TEMPLATES:     # the SF10 join cell's limits, one a query
        assert cfg["float_rel_limit"][t] == one["float_rel_limit"][t.partition(".")[0]]
    assert set(cfg["float_rel_limit_why"]) >= {"readings", "ties"}
    traffic = run.load_json(os.path.join(BENCH, "traffic", "joins_adhoc.json"))
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["trace_seconds"] == 6 and traffic["suite"] == "tpch_adhoc_joins"
    assert traffic["templates"] == TEMPLATES
    queries = suite("queries")
    assert list(queries.TEMPLATES) == TEMPLATES == list(adhoc_join_params.template_names())
    j10 = run.load_module(os.path.join(BENCH, "queries", "tpch_joins10.py"))
    for name, t in queries.TEMPLATES.items():   # what joinbytes.py reads is the join cell's
        theirs = j10.TEMPLATES[name.partition(".")[0]]
        assert {k: v for k, v in t.items() if k not in ("program", "filters")} \
            == {k: v for k, v in theirs.items() if k != "program"}
        assert set(t["filters"]) <= set(t["gathered"])


# ---- the draws -----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3, 2450000017])
def test_the_draws_are_distinct_inside_the_domains_and_a_function_of_the_seed(seed):
    q3, q5, q10 = adhoc_join_params.draws(seed)
    assert (q3, q5, q10) == adhoc_join_params.draws(seed) != adhoc_join_params.draws(seed + 1)
    for drawn in (q3, q5, q10):
        assert len(drawn) == len(set(drawn)) == adhoc_join_params.DRAWS == 4
    segments = {"AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"}
    regions = {"AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"}
    for p in q3:
        assert p.segment in segments
        assert datetime.date(1995, 3, 1) <= p.date <= datetime.date(1995, 3, 31)
    for p in q5:
        assert p.region in regions and 1993 <= p.year <= 1997
        assert (p.start, p.end) == (datetime.date(p.year, 1, 1), datetime.date(p.year + 1, 1, 1))
    for p in q10:
        assert datetime.date(1993, 2, 1) <= p.start <= datetime.date(1995, 1, 1)
        assert p.start.day == p.end.day == 1
        assert (p.end.year * 12 + p.end.month) - (p.start.year * 12 + p.start.month) == 3
    assert (len(adhoc_join_params.Q3_SEGMENTS) * len(adhoc_join_params.Q3_DAYS),
            len(adhoc_join_params.Q5_REGIONS) * len(adhoc_join_params.Q5_YEARS),
            len(adhoc_join_params.Q10_MONTHS)) == (155, 25, 24)
    assert adhoc_join_params.Q10_MONTHS[0] == (1993, 2)
    assert adhoc_join_params.Q10_MONTHS[-1] == (1995, 1)
    # the generator's own domains (benchmark/datagen/tpch.py), not a list of this file's
    gen = run.load_module(os.path.join(BENCH, "datagen", "tpch.py"))
    assert set(adhoc_join_params.Q3_SEGMENTS) == set(gen.SEGMENTS) == segments
    assert set(adhoc_join_params.Q5_REGIONS) == set(gen.REGIONS) == regions


def test_a_quarter_ends_three_months_on():
    assert adhoc_join_params.Q10(1993, 10).end == datetime.date(1994, 1, 1)
    assert adhoc_join_params.Q10(1994, 11).end == datetime.date(1995, 2, 1)
    assert adhoc_join_params.Q10(1995, 1).end == datetime.date(1995, 4, 1)


def test_the_generator_makes_the_runs_draws_and_the_join_cells_tables():
    arrow = suite("datagen").generate(0.002, 11, TABLES)
    assert adhoc_join_params.of("q3.p03") == adhoc_join_params.draws(11)[0][3]
    assert adhoc_join_params.of("q10.p01") == adhoc_join_params.draws(11)[2][1]
    same = run.load_module(os.path.join(BENCH, "datagen", "tpch_joins10.py")).generate(
        0.002, 11, TABLES)
    assert all(arrow[t].equals(same[t]) for t in TABLES)
    suite("datagen").generate(0.002, 12, ["region"])
    assert adhoc_join_params.of("q5.p00") == adhoc_join_params.draws(12)[1][0]


def test_the_validation_values_give_the_join_cells_answers():
    """The templates are `tpch_joins10`'s texts: with the specification's
    validation values (BUILDING, 1995-03-15; ASIA, 1994; October 1993) they
    give its answers, and the reference gives its reference's to the last
    bits of a sum."""
    import daft_tpu as dt

    arrow = suite("datagen").generate(0.01, 5, TABLES)
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    queries, ref = suite("queries"), suite("reference")
    j10 = run.load_module(os.path.join(BENCH, "queries", "tpch_joins10.py"))
    ref10 = run.load_module(os.path.join(BENCH, "reference", "tpch_joins10.py"))
    values = {"q3": adhoc_join_params.Q3("BUILDING", 15), "q5": adhoc_join_params.Q5("ASIA", 1994),
              "q10": adhoc_join_params.Q10(1993, 10)}
    for name, p in values.items():
        got = getattr(queries, name)(tables, p).to_pydict()
        assert got == j10.TEMPLATES[name]["program"](tables).to_pydict()
        numbers = compare.compare(ref10.answer(name, arrow), ref.answer_for(name, p, arrow))
        assert numbers["shape"] == 0 and numbers["exact_mismatches"] == 0
        assert numbers["float_rel_gap"] < 1e-14, (name, numbers)


# ---- through the harness ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2**31 + 45, 13])
def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root, seed):
    add_cell(bench_root, "tiny.joins_adhoc", "tiny", "joins_adhoc", scale_factor=0.05,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    result = run.run_cell(bench_root, "tiny.joins_adhoc", seed=seed, seconds=0.2,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 12
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}
    assert adhoc_join_params.of("q3.p00") == adhoc_join_params.draws(seed)[0][0]


@pytest.fixture(scope="module")
def small():
    """(cell, Arrow tables, program tables) at SF0.05 with seed 3's draws."""
    import daft_tpu as dt

    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, 3, cell.tables_read())
    return cell, arrow, {n: dt.from_arrow(t).collect() for n, t in arrow.items()}


def test_every_template_has_an_answer_of_its_own(small):
    """Four draws a query are four answers: a program (or a reference) that
    answered a template with another's values would be caught."""
    cell, arrow, _tables = small
    adhoc_join_params.set_seed(3)
    answers = {t: cell.reference.answer(t, arrow) for t in cell.templates}
    for q in ("q3", "q5", "q10"):
        mine = [repr(answers[t]) for t in cell.templates if t.startswith(q + ".")]
        assert len(set(mine)) == 4


@pytest.mark.parametrize("query", ["q3", "q5", "q10"])
def test_a_template_given_another_templates_values_is_not_correct(small, query):
    """The parameters' guarantee: the program's answer to the values of
    `<query>.p01`, put in `<query>.p00`'s place, fails the comparison that
    decides `correct`; its own template's passes."""
    cell, arrow, tables = small
    adhoc_join_params.set_seed(3)
    theirs = cell.queries.TEMPLATES[f"{query}.p01"]["program"](tables).to_pydict()
    lim = compare.limits(cell.config, f"{query}.p00")
    assert compare.within(compare.compare(cell.reference.answer(f"{query}.p01", arrow), theirs),
                          compare.limits(cell.config, f"{query}.p01"))
    assert not compare.within(
        compare.compare(cell.reference.answer(f"{query}.p00", arrow), theirs), lim)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """The reference with its float columns stored in bfloat16, put in the
    program's place, fails the cell's own float limits through q3 or q10 (at
    a test's scale; the readings at SF10 are in the configuration's file and
    PERF.md section 2)."""
    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = {}
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts[name] = compare.within(compare.compare(ref, low), lim)
    assert not all(verdicts[t] for t in cell.templates if not t.startswith("q5")), verdicts


# ---- the device tier: a value is an argument -------------------------------------------

_PER_VALUE = ("hbm_literal_rebuilds", "join_filter_program_traces", "join_provision_traces",
              "device_stage_program_traces", "hbm_cache_misses", "hbm_h2d_bytes")


def test_drawn_values_on_the_device_tier_rebuild_nothing_and_trace_nothing():
    """q3, q5 and q10 with four draws each, in turn, twice round, forced onto
    the device at a test's size (morsels small enough that a query takes
    several dispatches): every answer is the reference's and the host tier's;
    from the second template of a query on, no slot is rebuilt for a literal,
    no visibility, provisioning or stage program is traced, nothing is built
    and nothing uploaded (the literal arrays travel inside the calls); every
    execution passes its values, and q3 and q10 keep their fused TopN."""
    import daft_tpu as dt
    from daft_tpu.config import execution_config_ctx
    from daft_tpu.device.residency import manager
    from daft_tpu.observability.metrics import registry
    from daft_tpu.ops import counters

    manager().clear()
    cell = run.Cell(REPO, CELL)
    queries = suite("queries")   # a fresh module: nothing is checked off the TPU
    arrow = cell.datagen.generate(0.01, 7, cell.tables_read())
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    config = dict(morsel_size_rows=8192, pipeline_mode="force")
    seen, first_round = set(), {}
    for rnd in (1, 2):
        for name in cell.templates:
            query = name.partition(".")[0]
            program = queries.TEMPLATES[name]["program"]
            before = {c: registry().get(c) for c in _PER_VALUE}
            counters.reset()
            with execution_config_ctx(device_mode="on", **config):
                got = program(tables).to_pydict()
            grown = {c: registry().get(c) - before[c] for c in _PER_VALUE
                     if c not in counters.COUNTER_NAMES}
            grown.update({c: getattr(counters, c) for c in _PER_VALUE
                          if c in counters.COUNTER_NAMES})
            assert counters.device_join_batches > 1, (name, counters.rejections)
            assert counters.join_filter_literal_args == {"q3": 2, "q5": 3, "q10": 2}[query]
            if query in ("q3", "q10"):
                assert counters.device_topn_runs == 1, (name, counters.rejections)
                assert counters.device_join_topn_batches == counters.device_join_batches
            if query in seen:
                assert not any(grown.values()), (name, rnd, grown)
            seen.add(query)
            numbers = compare.compare(cell.reference.answer(name, arrow), got)
            assert compare.within(numbers, compare.limits(cell.config, name)), (name, numbers)
            if rnd == 1:
                with execution_config_ctx(device_mode="off", **config):
                    host = program(tables).to_pydict()
                numbers = compare.compare(host, got)
                assert compare.within(numbers, compare.limits(cell.config, name)), (name, numbers)
                first_round[name] = got
            else:
                assert got == first_round[name], name
    for query in ("q3", "q5", "q10"):   # (at this size two close dates can share a top ten)
        mine = {repr(a) for t, a in first_round.items() if t.startswith(query + ".")}
        assert len(mine) >= 3, (query, len(mine))
    manager().clear()


# ---- the suite's own check -------------------------------------------------------------

def test_a_program_without_the_counters_ends_the_run_at_import(monkeypatch, capsys):
    """The parent of the PR that added the cell: the suite exits 1 as it is
    imported, before any data is made, naming what is missing."""
    from daft_tpu.observability import metrics

    suite("queries")  # this program declares them
    monkeypatch.setattr(metrics, "DEVICE_COUNTER_NAMES", tuple(
        c for c in metrics.DEVICE_COUNTER_NAMES if c not in NEW_COUNTERS))
    with pytest.raises(SystemExit) as e:
        suite("queries")
    assert e.value.code == 1
    out = capsys.readouterr()
    assert "hbm_literal_rebuilds" in out.out and "join_filter_program_traces" in out.err
    assert "join_filter_literal_args" in out.err and CONFIG in out.err
    with pytest.raises(SystemExit):
        run.Cell(REPO, CELL)  # the harness finds the cell's files first of all


def _grown(**changed):
    base = dict(device_join_batches=58, device_topn_runs=1, device_join_topn_batches=58,
                device_topn_fetched_rows=10, hbm_literal_rebuilds=0,
                join_filter_program_traces=0, join_provision_traces=0,
                device_stage_program_traces=0)
    return dict(base, **changed)


@pytest.mark.parametrize("query, first, grown, why", [
    ("q3", False, _grown(), ""),                                         # the deployment
    ("q3", True, _grown(join_filter_program_traces=1, join_provision_traces=2,
                        device_stage_program_traces=3), ""),            # a query's first compiles
    ("q10", False, _grown(device_topn_fetched_rows=20), ""),
    ("q10", False, _grown(device_topn_fetched_rows=7), ""),              # fewer winners than the limit
    ("q5", False, _grown(device_topn_runs=0, device_join_topn_batches=0,
                         device_topn_fetched_rows=0), ""),              # q5 has no TopN
    ("q5", False, _grown(device_join_batches=0), "dispatched no join"),  # `auto` chose the host
    ("q3", False, _grown(device_topn_runs=0), "fused TopN"),             # per-batch tables
    ("q3", False, _grown(device_join_topn_batches=1), "fused TopN"),     # a TopN of one batch
    ("q10", False, _grown(device_topn_fetched_rows=2048), "fused TopN"),  # a table fetched
    ("q3", False, _grown(hbm_literal_rebuilds=3), "rebuilds a slot"),    # the parent's pack
    ("q5", False, _grown(join_filter_program_traces=1), "traces a program"),
    ("q10", False, _grown(join_provision_traces=1), "traces a program"),
    ("q3", False, _grown(device_stage_program_traces=1), "traces a program"),
])
def test_what_a_first_execution_has_to_have_done(query, first, grown, why):
    got = suite("queries")._why_not(query, first, grown)
    assert (got == "") if not why else (why in got), got


@pytest.mark.parametrize("backend, moved, ends", [
    ("cpu", {"q3.p01": dict(hbm_literal_rebuilds=3)}, None),      # tier-1 tests: nothing is checked
    ("tpu", {}, None),                                            # the deployment
    ("tpu", {"q3.p00": dict(join_filter_program_traces=1, hbm_literal_rebuilds=2)}, None),
    ("tpu", {"q3.p01": dict(hbm_literal_rebuilds=3)}, "q3.p01"),  # a pack rebuilt for a value
    ("tpu", {"q5.p01": dict(join_filter_program_traces=1)}, "q5.p01"),
    ("tpu", {"q5.p00": dict(device_join_batches=-58)}, "q5.p00"),  # its join ran on the host
])
def test_a_value_must_cost_no_rebuild_and_no_trace(monkeypatch, capsys, backend, moved, ends):
    import daft_tpu as dt
    import jax

    queries = suite("queries")  # a fresh module: a fresh count of builds
    arrow = suite("datagen").generate(0.002, 6, TABLES)
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    base = {c: 100 for c in queries._CHECKED}
    for name in ("q3.p00", "q3.p01", "q5.p00", "q5.p01"):
        query = name.partition(".")[0]
        after = _grown(**moved.get(name, {}))
        if query == "q5":
            after.update(device_topn_runs=0, device_join_topn_batches=0,
                         device_topn_fetched_rows=0)
        counts = iter([base, {c: base[c] + after[c] for c in base}])
        monkeypatch.setattr(queries, "_counts", lambda: next(counts))
        program = queries.TEMPLATES[name]["program"]
        program(tables)  # built, never executed
        if name == ends:
            with pytest.raises(SystemExit) as e:
                program(tables)
            assert e.value.code == 1
            out = capsys.readouterr()
            assert f"{name}'s first execution" in out.out and CONFIG in out.err
            return
        program(tables)
        program(tables)  # a third build checks nothing and reads no counter
    assert ends is None


def test_a_program_that_rebuilds_for_a_value_ends_the_run_in_warm_up(bench_root, monkeypatch,
                                                                    capsys):
    """Through the harness: on a TPU backend a program whose every execution
    rebuilds a slot for its literals ends the run in warm-up, at the second
    template of the first query, before the window."""
    import jax
    from daft_tpu.ops import counters

    add_cell(bench_root, "tiny.joins_adhoc", "tiny", "joins_adhoc", scale_factor=0.01,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    real, calls = counters.snapshot, [0]

    def snapshot():
        # as if every execution so far had dispatched its join, kept its TopN
        # and rebuilt a pack of its own
        calls[0] += 1
        return dict(real(), hbm_literal_rebuilds=calls[0], device_join_batches=58 * calls[0],
                    device_topn_runs=calls[0], device_join_topn_batches=58 * calls[0],
                    device_topn_fetched_rows=10 * calls[0])

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(counters, "snapshot", snapshot)
    with pytest.raises(SystemExit) as e:
        run.run_cell(bench_root, "tiny.joins_adhoc", seed=5, seconds=0.2,
                     trace=False, require_tpu=False)
    assert e.value.code == 1
    out = capsys.readouterr().out
    assert "q3.p01's first execution" in out and "hbm_literal_rebuilds" in out
    assert '"phase": "window"' not in out


# ---- the readers -----------------------------------------------------------------------

ORDERS = 1_500_000                  # rows: padded to 2^21
PLANE = 4 * (1 << 21)
# three executions: a q3 (0..10 s), a q5 (10..20 s), a q10 (20..30 s); each runs
# its visibility program once (0.01, 0.02 and 0.01 s of operations inside the
# module's 0.02, 0.03 and 0.01 s) before its join dispatches
TRACE = {"sync_s": 0.0, "device": {"/device:TPU:0": {
    "XLA Modules": [("jit_join_filter_verdict(123)", 0.5, 0.02), ("jit_run(7)", 1.0, 4.0),
                    ("jit_join_filter_verdict(123)", 10.5, 0.03), ("jit_run(8)", 11.0, 2.0),
                    ("jit_join_filter_verdict(456)", 20.5, 0.01), ("jit_run(9)", 21.0, 5.0)],
    "XLA Ops": [("fusion.1", 0.5, 0.01), ("while.5", 1.0, 4.0),
                ("fusion.1", 10.5, 0.01), ("fusion.2", 10.52, 0.01), ("fusion.3", 11.0, 2.0),
                ("fusion.9", 20.5, 0.01), ("while.7", 21.0, 5.0)]}}}


def _counters(args, topn_rows=None, **more):
    c = {"device_join_batches": 58, "device_grouped_batches": 58,
         "join_filter_literal_args": args}
    if topn_rows:
        c.update(device_topn_runs=1, device_join_topn_batches=58,
                 device_topn_fetched_rows=topn_rows)
    return dict(c, **more)


RUNS = [
    {"template": "q3.p02", "unix_start": 0.0, "unix_end": 10.0, "start": 0.0, "end": 10.0,
     "failed": False, "counters": _counters(2, 10)},
    {"template": "q5.p00", "unix_start": 10.0, "unix_end": 20.0, "start": 10.0, "end": 20.0,
     "failed": False, "counters": _counters(3)},
    {"template": "q10.p03", "unix_start": 20.0, "unix_end": 30.0, "start": 20.0, "end": 30.0,
     "failed": False, "counters": _counters(2, 20)},
]
SPANS = [("query", 0.0, 10.0), ("placement.decide", 0.1, 0.2),
         ("device.dispatch", 0.4, 1.4), ("join.gather", 0.45, 0.9), ("join.filter", 0.5, 0.6),
         ("device.launch", 1.0, 1.3),
         ("device.dispatch", 2.0, 2.5), ("join.gather", 2.1, 2.3), ("device.launch", 2.3, 2.4),
         ("stage.finalize", 8.0, 9.5), ("join.topn_select", 8.1, 9.1),
         ("query", 10.0, 20.0), ("device.dispatch", 10.4, 11.0), ("join.gather", 10.45, 10.8),
         ("join.filter", 10.5, 10.7), ("device.launch", 10.8, 10.9),
         ("query", 20.0, 30.0), ("device.dispatch", 20.4, 21.0), ("join.gather", 20.45, 20.7),
         ("join.filter", 20.5, 20.6), ("device.launch", 20.8, 20.9),
         ("stage.finalize", 27.0, 29.0), ("join.topn_select", 27.5, 28.0),
         ("join.filter", 40.0, 41.0)]      # outside every execution
ARRAYS = [((1 << 21,), "float32", PLANE)] * 3 + [((1 << 21,), "int32", PLANE)] * 2 \
    + [((1 << 21,), "bool", 1 << 21)] * 4 + [((131072,), "float32", 4 * 131072)] * 9 \
    + [((131072,), "bool", 131072)] * 20


def ctx_of(trace=TRACE, runs=RUNS, spans=SPANS, hbm_bytes_per_s=1e10):
    import xtrace as tr

    return {"executions": list(runs), "spans": list(spans), "trace": trace,
            "busy": tr.busy_union(trace), "window": (0.0, 30.0), "to_trace": 0.0,
            "window_s": 30.0, "queries": suite("queries").TEMPLATES,
            "rows": {"orders": ORDERS, "lineitem": 6_000_000},
            "peaks": {"hbm_bytes_per_s": hbm_bytes_per_s, "f32_flops_per_s": 1e12}}


def _live(monkeypatch, arrays):
    import joinbytes

    monkeypatch.setattr(joinbytes, "live_planes", lambda: arrays)


@pytest.mark.parametrize("name, want", [
    ("adhocjoin.literal_rebuilds_per_query", 0.0),
    ("adhocjoin.filter_args_per_query", 7 / 3),
    ("adhocjoin.filter_ms", 1e3 * (0.1 + 0.2 + 0.1) / 3),
    ("adhocjoin.batches_per_query", 58.0),
    ("adhocjoin.residency_misses", 0.0),
    ("adhocjoin.select_ms", 1e3 * (1.0 + 0.5) / 2),
    ("adhocjoin.launch_ms", 1e3 * (0.3 + 0.1 + 0.1 + 0.1) / 4),
    ("adhocjoin.dispatch_host_ms", 1e3 * (1.0 + 0.5 + 0.6 + 0.6) / 4),
    ("adhocjoin.decide_ms", 1e3 * 0.1 / 3),
])
def test_the_readers_on_a_hand_made_window(name, want):
    assert reader(name).read(ctx_of()) == pytest.approx(want)


def test_what_a_rebuild_a_query_reads():
    """The parent's path, had it the counter: a pack, a visibility plane and
    a host plane rebuilt by every execution."""
    rebuilt = [dict(r, counters=dict(r["counters"], hbm_literal_rebuilds=3)) for r in RUNS]
    assert reader("adhocjoin.literal_rebuilds_per_query").read(ctx_of(runs=rebuilt)) == 3.0


def test_the_trace_count_is_the_whole_processs():
    from daft_tpu.ops import counters

    assert reader("adhocjoin.filter_program_traces").read(ctx_of()) \
        == counters.snapshot()["join_filter_program_traces"]


@pytest.mark.parametrize("twin, accepted", sorted(TWINS.items()))
def test_a_twin_reads_what_the_accepted_reader_reads(monkeypatch, twin, accepted):
    """One arithmetic under two names (`benchmark/twin.py`): on a window with
    every span and counter the accepted readers look for, the twin gives
    their number."""
    _live(monkeypatch, ARRAYS)
    runs = [dict(r, counters=dict(r["counters"], hbm_cache_misses=k)) for k, r in enumerate(RUNS)]
    ctx = ctx_of(runs=runs)
    got = reader(twin).read(ctx)
    assert got is not None and got == reader(accepted).read(ctx)


def test_the_filter_roofline_counts_the_planes_read_and_the_verdict_written(monkeypatch, capsys):
    """q3 and q5 read two planes as long as `orders` padded and write one, q10
    reads one (benchmark/filterbytes.py), against the seconds of the
    visibility programs' own operations inside each execution."""
    import filterbytes

    queries = suite("queries").TEMPLATES
    assert [queries[t]["filters"] for t in ("q3.p00", "q5.p00", "q10.p00")] \
        == [{"orders": 2}, {"orders": 2}, {"orders": 1}]
    assert filterbytes.padded(ORDERS) == 1 << 21 and filterbytes.padded(5) == 512
    rows = {"orders": ORDERS}
    assert filterbytes.least_bytes(queries["q3.p01"], ARRAYS, rows) == 3 * PLANE
    assert filterbytes.least_bytes(queries["q10.p01"], ARRAYS, rows) == 2 * PLANE
    assert filterbytes.least_bytes({"program": None}, ARRAYS, rows) is None
    assert filterbytes.least_bytes(queries["q3.p01"], ARRAYS[5:], rows) is None   # not resident
    assert filterbytes.program_seconds(TRACE, (0.0, 10.0)) == pytest.approx(0.01)
    assert filterbytes.program_seconds(TRACE, (10.0, 20.0)) == pytest.approx(0.02)
    assert filterbytes.program_seconds(TRACE, (1.0, 10.0)) == 0.0
    _live(monkeypatch, ARRAYS)
    share = reader("adhocjoin.filter_hbm_share").read(ctx_of())
    least = (3 + 3 + 2) * PLANE / 1e10
    assert share == pytest.approx(100.0 * least / (0.01 + 0.02 + 0.01)) and 0 < share < 100
    said = [json.loads(x) for x in capsys.readouterr().out.splitlines()][-1]
    assert said["phase"] == "roofline" and said["filter_least_bytes"] == 8 * PLANE
    assert said["bound"] == "hbm"


def test_the_filter_roofline_cannot_pass_100_percent(monkeypatch):
    """The chip cannot move the planes faster than its HBM gives them: with
    the programs' operations exactly as long as the least time, the share is
    100%; operations outside the program's modules, however long, are never
    divided by, and where no module is named there is nothing to read."""
    _live(monkeypatch, ARRAYS)
    bw = 1e10
    q3_s, q10_s = 3 * PLANE / bw, 2 * PLANE / bw
    exact = {"sync_s": 0.0, "device": {"/device:TPU:0": {
        "XLA Modules": [("jit_join_filter_verdict(1)", 0.5, q3_s), ("jit_run(7)", 1.0, 4.0),
                        ("jit_join_filter_verdict(2)", 20.5, q10_s)],
        "XLA Ops": [("fusion.1", 0.5, q3_s), ("while.5", 1.0, 4.0), ("fusion.9", 20.5, q10_s)]}}}
    rd = reader("adhocjoin.filter_hbm_share")
    runs = [RUNS[0], RUNS[2]]
    assert rd.read(ctx_of(exact, runs)) == pytest.approx(100.0)
    # a plane without an operations line: the modules' own lengths stand in
    modules_only = {"sync_s": 0.0, "device": {"/device:TPU:0": {
        "XLA Modules": exact["device"]["/device:TPU:0"]["XLA Modules"]}}}
    assert rd.read(ctx_of(modules_only, runs)) == pytest.approx(100.0)
    half = json.loads(json.dumps(exact))
    half["device"]["/device:TPU:0"]["XLA Modules"][0][2] = 2 * q3_s
    half["device"]["/device:TPU:0"]["XLA Ops"][0][2] = 2 * q3_s
    assert rd.read(ctx_of(half, [RUNS[0]])) == pytest.approx(50.0)
    unnamed = {"sync_s": 0.0, "device": {"/device:TPU:0": {
        "XLA Ops": exact["device"]["/device:TPU:0"]["XLA Ops"]}}}
    assert rd.read(ctx_of(unnamed, runs)) is None
    failed = [dict(r, failed=True) for r in runs]
    assert rd.read(ctx_of(exact, failed)) is None


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_a_program_without_the_counters_and_the_span_gives_nothing_to_read(name, monkeypatch):
    """The parent's program: no `hbm_literal_rebuilds`, no `join_filter_*`
    counter, no `join.filter` span, no visibility program in the trace: None,
    not a raise, so the result line leaves the metric out."""
    from daft_tpu.ops import counters

    _live(monkeypatch, ARRAYS)
    real = counters.snapshot
    monkeypatch.setattr(counters, "snapshot", lambda: {
        k: v for k, v in real().items() if k not in NEW_COUNTERS})
    runs = [dict(r, counters={k: v for k, v in r["counters"].items() if k not in NEW_COUNTERS})
            for r in RUNS]
    spans = [s for s in SPANS if s[0] != "join.filter"]
    trace = {"sync_s": 0.0, "device": {"/device:TPU:0": {
        line: [e for e in events if not e[0].startswith("jit_join_filter_verdict")]
        for line, events in TRACE["device"]["/device:TPU:0"].items()}}}
    assert reader(name).read(ctx_of(trace, runs, spans)) is None


def test_the_list_less_readers_read_the_cells_window_true():
    """100% of the queries on the device, 58 dispatches each, nothing
    uploaded."""
    ctx = ctx_of()
    assert reader("placement.device_query_share").read(ctx) == 100.0
    assert reader("stages.dispatches_per_query").read(ctx) == 58.0
    assert reader("h2d.bytes_per_query").read(ctx) == 0
