"""The `tpch_filtered_joins` suite (`tpch_sf10.filtered_joins`): the entries,
held BY NAME, with what `test_bench_adhoc_joins.py` held of the ad-hoc join
cell by position (`tests/conftest.py`); the configuration's deployment,
guarantees, `reduced` and `assumed`; the templates are `queries/tpch.py`'s q12
and q19 and `queries/tpch_joins10.py`'s q14 letter for letter, and the
reference, which stands alone, gives those suites' references' answers; the
cell's traffic is correct through the harness at a test's size; the bfloat16
control is not correct (through q19); on the device tier the three templates
give the reference's answers, q14 and q19 gather `part`'s whole pack at every
dispatch, a repeat misses nothing, and `auto` prices each at the dispatch it
delivers; the suite refuses a program without the two counters and a warm-up
that ran on the host, gathered from a window or missed a slot on a repeat; and
the `filteredjoin.*` readers on a hand-made window and on the recorded chip
trace (a roofline share that cannot pass 100%), each None where there is
nothing to read. On the CPU: nothing here is a measurement."""

import json
import os

import pytest

import compare
import run
from bench_helpers import BENCH, REPO, add_cell

CELL = "tpch_sf10.filtered_joins"
CONFIG = "tpch-sf10-filtered-joins-1chip"
PROGRAM_METRICS = ["filteredjoin.priced_over_delivered",
                   "filteredjoin.unwindowed_gathers_per_query",
                   "filteredjoin.batches_per_query", "filteredjoin.membership_ms"]
TWINS = {"filteredjoin.gather_ms": "join.gather_ms",
         "filteredjoin.dispatch_host_ms": "stages.dispatch_host_ms",
         "filteredjoin.launch_ms": "stages.launch_ms",
         "filteredjoin.decide_ms": "placement.decide_ms",
         "filteredjoin.residency_misses": "residency.misses_per_query"}
METRICS = PROGRAM_METRICS + list(TWINS) + ["filteredjoin.join_hbm_share"]
LIST_LESS = {"placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
             "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"}
TEMPLATES = ["q12", "q14", "q19"]
NEW_COUNTERS = ("join_priced_dispatch_rows", "join_unwindowed_gathers")
TABLES = ["part", "orders", "lineitem"]
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def suite(kind, name="tpch_filtered_joins"):
    return run.load_module(os.path.join(BENCH, kind, name + ".py"))


# ---- the entries and the configuration ---------------------------------------------------

def test_the_cell_and_its_metrics_are_entries_by_name():
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    assert by_name[CELL] == dict(by_name[CELL], config=CONFIG, traffic="joins_filtered", chips=1)
    assert len(by_name[CELL]["why"]) <= 200
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["scale_factor"] and len(config["source"]) <= 200
    assert len(config["why"]) <= 200
    # one use of a configuration's file, and of a (configuration, traffic) pair
    assert [c["file"] for c in spec["configs"]].count(config["file"]) == 1
    assert [(w["config"], w["traffic"]) for w in spec["workloads"]].count(
        (CONFIG, "joins_filtered")) == 1
    cell = run.Cell(REPO, CELL)
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    mine = [m for m in spec["per_layer"] if m["name"].startswith("filteredjoin.")]
    assert [m["name"] for m in mine] == METRICS
    assert all(m["workloads"] == [CELL] for m in mine)
    assert all(set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
               for m in mine)
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    assert [(per_layer[n]["layer"], per_layer[n]["source"], per_layer[n]["unit"],
             per_layer[n]["better"]) for n in PROGRAM_METRICS + METRICS[-1:]] == [
        ("Placement", "program_counter", "ratio", "higher"),
        ("Device stages", "program_counter", "count", "lower"),
        ("Device stages", "program_counter", "count", "lower"),
        ("Device stages", "program_span", "ms", "lower"),
        ("Kernels", "device_trace", "%", "higher")]
    assert all(m["moves"] == "query_ms.geomean" for m in mine)
    for new, old in TWINS.items():   # a twin says of its reading what the accepted metric says
        assert {k: per_layer[new][k] for k in ("unit", "better", "source", "layer", "moves")} \
            == {k: per_layer[old][k] for k in ("unit", "better", "source", "layer", "moves")}
        assert CELL not in per_layer[old].get("workloads", [CELL])
    assert {m["name"] for m in cell.metrics("per_layer")} == set(METRICS) | LIST_LESS
    # no list that was there took the cell
    assert all(CELL not in m.get("workloads", []) for m in spec["per_layer"] + spec["end_to_end"]
               if not m["name"].startswith("filteredjoin."))
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    # the entries follow PR 45's, in their order; two cells of nine or more on four chips
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index("adhocjoin.decide_ms") + 1 == names.index(METRICS[0])
    assert names[names.index(METRICS[0]):][:len(METRICS)] == METRICS
    cells = [w["name"] for w in spec["workloads"]]
    assert cells[cells.index("tpch_sf10.adhoc_joins"):][:2] == ["tpch_sf10.adhoc_joins", CELL]
    configs = [c["name"] for c in spec["configs"]]
    assert configs[configs.index("tpch-sf10-adhoc-joins-1chip"):][:2] \
        == ["tpch-sf10-adhoc-joins-1chip", CONFIG]
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] \
        == ["tpch_sf30_mesh4.scanagg", "tpch_sf30_mesh4.joins"]


def test_the_ad_hoc_join_cells_entries_are_as_they_were():
    """What `test_bench_adhoc_joins.py::test_the_cell_and_its_metrics_are_
    entries_by_name` and `::test_the_join_cells_entries_are_as_they_were` hold
    besides the END of the lists and a count of eight cells (PR 45's entries
    stood last until this cell's followed them: tests/conftest.py), kept by
    name."""
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    adhoc = next(w for w in spec["workloads"] if w["name"] == "tpch_sf10.adhoc_joins")
    assert (adhoc["config"], adhoc["traffic"], adhoc["chips"]) \
        == ("tpch-sf10-adhoc-joins-1chip", "joins_adhoc", 1)
    theirs = [m for m in spec["per_layer"] if m["name"].startswith("adhocjoin.")]
    assert len(theirs) == 13 and all(m["workloads"] == ["tpch_sf10.adhoc_joins"] for m in theirs)
    cell = run.Cell(REPO, "tpch_sf10.adhoc_joins")
    assert {m["name"] for m in cell.metrics("per_layer")} \
        == {m["name"] for m in theirs} | LIST_LESS
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index("jointopn.join_hbm_share") + 1 == names.index("meshjoin.shards_per_dispatch")
    assert names.index("meshjoin.join_hbm_share") + 1 \
        == names.index("adhocjoin.literal_rebuilds_per_query")
    cells = [w["name"] for w in spec["workloads"]]
    assert cells[cells.index("tpch_sf10.joins"):][:3] \
        == ["tpch_sf10.joins", "tpch_sf30_mesh4.joins", "tpch_sf10.adhoc_joins"]
    configs = [c["name"] for c in spec["configs"]]
    assert configs[configs.index("tpch-sf10-joins-1chip"):][:3] \
        == ["tpch-sf10-joins-1chip", "tpch-sf30-joins-4chip", "tpch-sf10-adhoc-joins-1chip"]
    for name in ("tpch_sf10.joins", "tpch_sf30_mesh4.joins", "tpch_sf1.joins"):
        reported = {m["name"] for m in run.Cell(REPO, name).metrics("per_layer")}
        assert not {m for m in reported if m.startswith("filteredjoin.")}


def test_the_configuration_states_the_deployment():
    cell = run.Cell(REPO, CELL)
    cfg = cell.config
    one = run.load_json(os.path.join(BENCH, "configs", "tpch-sf10-joins-1chip.json"))
    assert cfg["name"] == CONFIG and cfg["suite"] == "tpch_filtered_joins"
    assert cfg["scale_factor"] == one["scale_factor"] == 10 and cfg["chips"] == 1
    assert cfg["source_scale_factor"] == 100 and list(cfg["reduced"]) == ["scale_factor"]
    assert cfg["reduced"] == one["reduced"]
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert cfg["source"] != one["source"]
    for clause in ("2.4.12", "2.4.14", "2.4.19", "SF10", "1.2", "4.2.3"):
        assert clause in cfg["source"]
        assert clause in next(c for c in spec["configs"] if c["name"] == CONFIG)["source"]
    # tpch-sf10-joins-1chip's three guarantees word for word but for the reference's file
    assert set(cfg["guarantees"]) == set(one["guarantees"]) == {"answers", "exact", "floats"}
    assert cfg["guarantees"]["exact"] == one["guarantees"]["exact"]
    assert cfg["guarantees"]["floats"] == one["guarantees"]["floats"]
    assert cfg["guarantees"]["answers"] == one["guarantees"]["answers"].replace(
        "reference/tpch_joins10.py", "reference/tpch_filtered_joins.py")
    assert cfg["assumed"][0] == one["assumed"][0] and len(cfg["assumed"]) == 2
    assert "lineitem, orders, part" in cfg["assumed"][1]
    for word in ("lineitem, orders, part", "from_arrow().collect()", "membership planes",
                 "device_mode auto", "no DAFT_TPU_* variable"):
        assert word in cfg["deployment"], word
    assert list(cfg["float_rel_limit"]) == TEMPLATES == cell.templates
    assert set(cfg["float_rel_limit_why"]) >= {"readings", "q12", "q14", "q19"}
    traffic = run.load_json(os.path.join(BENCH, "traffic", "joins_filtered.json"))
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["trace_seconds"] == 6 and traffic["suite"] == "tpch_filtered_joins"
    assert traffic["templates"] == TEMPLATES
    assert cell.tables_read() == ["orders", "lineitem", "part"]


def test_the_templates_are_the_other_suites_own_letter_for_letter():
    """q12 and q19 are `queries/tpch.py`'s functions and q14 is
    `queries/tpch_joins10.py`'s, loaded by path: the same code objects' text,
    and the same answers."""
    import daft_tpu as dt

    queries = suite("queries")
    tpch, j10 = suite("queries", "tpch"), suite("queries", "tpch_joins10")
    theirs = {"q12": tpch.q12, "q14": j10.q14, "q19": tpch.q19}
    assert list(queries.TEMPLATES) == TEMPLATES
    arrow = suite("datagen").generate(0.01, 5, TABLES)
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    for name in TEMPLATES:
        assert queries._QUERIES[name].__code__.co_code == theirs[name].__code__.co_code
        assert queries._QUERIES[name].__code__.co_consts == theirs[name].__code__.co_consts
        assert queries.TEMPLATES[name]["program"](tables).to_pydict() \
            == theirs[name](tables).to_pydict()
    assert queries.TEMPLATES["q12"]["tables"] == tpch.TEMPLATES["q12"]["tables"]
    assert queries.TEMPLATES["q19"]["tables"] == tpch.TEMPLATES["q19"]["tables"]
    assert queries.TEMPLATES["q14"]["tables"] == j10.TEMPLATES["q14"]["tables"]
    # what filteredjoinbytes.py reads: only a dimension the fact does not follow is unwindowed
    for name, t in queries.TEMPLATES.items():
        assert set(t["unwindowed"]) <= set(t["gathered"]) and len(t["fact_columns"]) == 3
        assert t["unwindowed"] == (("part",) if name != "q12" else ())


def test_the_reference_stands_alone_and_gives_the_other_references_answers():
    """`reference/tpch_filtered_joins.py` imports nothing of the program and
    no other file of the benchmark; its Q12 and Q19 are `reference/tpch.py`'s
    and its Q14 `reference/tpch_joins10.py`'s, to the last bit, with and
    without the control's rounding."""
    with open(os.path.join(BENCH, "reference", "tpch_filtered_joins.py")) as f:
        text = f.read()
    assert "daft_tpu" not in text.replace("importing nothing of `daft_tpu`", "") \
        .replace("Independent of `daft_tpu`", "")
    assert "importlib" not in text and "spec_from_file_location" not in text
    ref, tpch, j10 = suite("reference"), suite("reference", "tpch"), suite("reference",
                                                                           "tpch_joins10")
    arrow = suite("datagen").generate(0.02, 9, TABLES)
    for storage in (None, ref.to_bfloat16):
        theirs = tpch.to_bfloat16 if storage else None
        assert ref.answer("q12", arrow, storage) == tpch.answer("q12", arrow, theirs)
        assert ref.answer("q19", arrow, storage) == tpch.answer("q19", arrow, theirs)
        assert ref.answer("q14", arrow, storage) == j10.answer("q14", arrow, theirs)
    assert list(ref.TEMPLATES) == TEMPLATES


def test_the_generator_makes_the_join_cells_tables_and_no_other():
    gen = suite("datagen")
    arrow = gen.generate(0.002, 11)
    assert sorted(arrow) == sorted(TABLES) and gen.TABLES == ("part", "orders", "lineitem")
    same = suite("datagen", "tpch_joins10").generate(0.002, 11, TABLES)
    assert all(arrow[t].equals(same[t]) for t in TABLES)


# ---- through the harness ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2**31 + 49, 17])
def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root, seed):
    add_cell(bench_root, "tiny.joins_filtered", "tiny", "joins_filtered", scale_factor=0.05,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    result = run.run_cell(bench_root, "tiny.joins_filtered", seed=seed, seconds=0.2,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}


@pytest.fixture(scope="module")
def small():
    """(cell, Arrow tables, program tables) at SF0.05."""
    import daft_tpu as dt

    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, 3, cell.tables_read())
    return cell, arrow, {n: dt.from_arrow(t).collect() for n, t in arrow.items()}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """The reference with its float columns stored in bfloat16, put in the
    program's place, fails the cell's own float limits through q19 (q12 has
    no float, and q14's ratio cancels its roundings), at a test's scale; the
    readings at SF10 are in the configuration's file and PERF.md section 2."""
    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = {}
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts[name] = compare.within(compare.compare(ref, low), lim)
    assert verdicts["q12"] is True and verdicts["q19"] is False, verdicts


# ---- the device tier ---------------------------------------------------------------------

# (`dict_encode_us`: q12 groups by a fact column, whose ranges' dictionaries
# are kept on the rows: a repeat encodes nothing on the host)
_WARM = ("hbm_cache_misses", "hbm_h2d_bytes", "join_provision_traces",
         "join_filter_program_traces", "device_stage_program_traces", "dict_encode_us")
_COUNTED = ("device_join_batches", "join_unwindowed_gathers", "join_window_gathers") + _WARM


def _grown(program, tables, **config):
    from daft_tpu.config import execution_config_ctx
    from daft_tpu.ops import counters

    before = counters.snapshot()
    with execution_config_ctx(morsel_size_rows=8192, pipeline_mode="force", **config):
        got = program(tables).to_pydict()
    after = counters.snapshot()
    return got, {k: after.get(k, 0) - before.get(k, 0) for k in _COUNTED + NEW_COUNTERS}


@pytest.mark.parametrize("name", TEMPLATES)
def test_the_program_answers_the_suite_on_the_device_tier(small, name):
    """A template forced onto the device at a test's size, morsels small
    enough that it takes several dispatches of eight segments: the
    reference's answer (shape 0, exact 0, floats inside the limit) and the
    host tier's; q14's and q19's dispatches each gather `part`'s whole pack,
    q12's read a window of `orders`'; a repeat misses no slot, uploads nothing
    and traces no program."""
    from daft_tpu.device.residency import manager

    cell, arrow, tables = small
    program = suite("queries").TEMPLATES[name]["program"]    # a fresh module: nothing checked
    manager().clear()
    host, _ = _grown(program, tables, device_mode="off")
    got, cold = _grown(program, tables, device_mode="on")
    numbers = compare.compare(cell.reference.answer(name, arrow), got)
    assert numbers["shape"] == 0 and numbers["exact_mismatches"] == 0, numbers
    assert compare.within(numbers, compare.limits(cell.config, name)), (name, numbers)
    assert compare.within(compare.compare(host, got), compare.limits(cell.config, name))
    lineitem = arrow["lineitem"].num_rows
    assert cold["device_join_batches"] == -(-lineitem // (8 * 8192)) > 1
    whole = cold["device_join_batches"] if name != "q12" else 0
    assert cold["join_unwindowed_gathers"] == whole
    assert cold["join_window_gathers"] == cold["device_join_batches"] - whole
    again, warm = _grown(program, tables, device_mode="on")
    assert again == got and not {k: warm[k] for k in _WARM if warm[k]}, warm
    assert warm["device_join_batches"] == cold["device_join_batches"]
    manager().clear()


@pytest.mark.parametrize("name", TEMPLATES)
def test_auto_prices_a_template_at_the_dispatch_it_delivers(small, name, monkeypatch):
    """Under `auto`, the backend said to be a TPU (the CPU's own probed terms
    price the device tier ahead): the template's join is dispatched on the
    device in ranges of eight morsels, and the rows a dispatch its chosen arm
    was priced at are those ranges' rows, which is what
    `filteredjoin.priced_over_delivered` reads as 1."""
    import jax
    from daft_tpu.device.residency import manager
    from daft_tpu.execution import executor

    cell, arrow, tables = small
    program = suite("queries").TEMPLATES[name]["program"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    executor._DECISION_CACHE.clear()
    # (one chip: the tests' eight virtual devices would have `auto` price a mesh)
    manager().clear()
    got, grown = _grown(program, tables, device_mode="auto", device_min_rows=1, mesh_devices=1)
    # what the suite's warm-up check asks of a second execution, by `auto`'s own road
    again, warm = _grown(program, tables, device_mode="auto", device_min_rows=1, mesh_devices=1)
    executor._DECISION_CACHE.clear()
    manager().clear()
    assert again == got and not {k: warm[k] for k in _WARM if warm[k]}, warm
    assert warm["join_priced_dispatch_rows"] == grown["join_priced_dispatch_rows"]
    assert compare.within(compare.compare(cell.reference.answer(name, arrow), got),
                          compare.limits(cell.config, name))
    assert grown["device_join_batches"] > 1
    assert grown["join_priced_dispatch_rows"] == 8 * 8192
    execution = {"template": name, "failed": False, "counters": grown}
    ratio = reader("filteredjoin.priced_over_delivered").read(
        {"executions": [execution], "rows": {t: a.num_rows for t, a in arrow.items()},
         "queries": suite("queries").TEMPLATES})
    full = arrow["lineitem"].num_rows / (8 * 8192)
    assert ratio == pytest.approx(-(-full // 1) / full) and 1.0 <= ratio < 1.25


# ---- the suite's own check -------------------------------------------------------------------

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
    assert "join_priced_dispatch_rows" in out.out and "join_unwindowed_gathers" in out.err
    with pytest.raises(SystemExit):
        run.Cell(REPO, CELL)  # the harness finds the cell's files first of all


ZERO = {c: 0 for c in ("device_join_batches", "join_unwindowed_gathers", "hbm_cache_misses",
                       "join_provision_traces", "join_filter_program_traces",
                       "device_stage_program_traces")}


@pytest.mark.parametrize("name, execution, grown, why", [
    ("q12", 1, {"device_join_batches": 58}, ""),
    ("q12", 1, {}, "dispatched no join on the device"),
    ("q19", 1, {"device_join_batches": 58, "join_unwindowed_gathers": 58}, ""),
    ("q19", 1, {"device_join_batches": 58}, "did not each gather from the whole of part's pack"),
    ("q14", 1, {"device_join_batches": 58, "join_unwindowed_gathers": 57},
     "join_unwindowed_gathers 57 over device_join_batches 58"),
    ("q14", 1, {"device_join_batches": 58, "join_unwindowed_gathers": 58,
                "hbm_cache_misses": 400, "join_provision_traces": 2}, ""),   # a first execution builds
    ("q14", 2, {"device_join_batches": 58, "join_unwindowed_gathers": 58}, ""),
    ("q14", 2, {"device_join_batches": 58, "hbm_cache_misses": 3}, "missed a resident slot"),
    ("q12", 2, {"device_join_batches": 58, "join_provision_traces": 1}, "traced a program"),
    ("q12", 2, {}, ""),     # (a second execution's placement is the first's: not asked again)
])
def test_what_a_warm_up_execution_has_to_have_done(name, execution, grown, why):
    said = suite("queries")._why_not(name, execution, dict(ZERO, **grown))
    assert (why in said) if why else said == "", said


@pytest.mark.parametrize("backend, moved, ends", [
    ("cpu", {}, None),                                                # tier-1 tests: nothing is checked
    ("tpu", {("q12", 1): {"device_join_batches": 58},
             ("q12", 2): {"device_join_batches": 58}}, None),         # the deployment
    ("tpu", {}, "q12's warm-up execution 1"),                         # the host tier
    ("tpu", {("q12", 1): {"device_join_batches": 58},
             ("q12", 2): {"device_join_batches": 58, "hbm_cache_misses": 1}},
     "q12's warm-up execution 2"),
])
def test_a_warm_up_off_the_device_or_a_cold_repeat_ends_the_run(monkeypatch, capsys, backend,
                                                                 moved, ends):
    """The suite looks at an execution when the next program is built: q12's
    first when q12 is built again, its second when q14 is built."""
    import daft_tpu as dt
    import jax

    queries = suite("queries")  # a fresh module: a fresh count of builds
    arrow = suite("datagen").generate(0.002, 6, TABLES)
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    now = dict(ZERO)
    monkeypatch.setattr(queries, "_counts", lambda: dict(now))

    def build(name, execution):
        queries.TEMPLATES[name]["program"](tables)     # built, never executed
        for c, n in moved.get((name, execution), {}).items():
            now[c] += n

    steps = [("q12", 1), ("q12", 2), ("q14", 1)]
    try:
        for step in steps:
            build(*step)
    except SystemExit as e:
        assert e.code == 1 and ends is not None
        out = capsys.readouterr()
        assert ends in out.out and ends in out.err and CONFIG in out.err
        return
    assert ends is None


# ---- the readers -----------------------------------------------------------------------------

PLANE = (1 << 20) * 4                 # a dispatch's plane of 4-byte items
PART, ORDERS, LINEITEM = 2_000_000, 15_000_000, 59_986_052
ROWS = {"part": PART, "orders": ORDERS, "lineitem": LINEITEM}
ARRAYS = [((1 << 20,), "float32", PLANE)] * 9 + [((1 << 20,), "int32", PLANE)] * 4 \
    + [((1 << 20,), "bool", 1 << 20)] * 12 + [((1 << 24,), "int32", (1 << 24) * 4)] \
    + [((2, 1 << 21), "float32", 2 * (1 << 21) * 4), ((16, 1 << 21), "float32", 16 * (1 << 21) * 4),
       ((15, 1 << 21), "float32", 15 * (1 << 21) * 4), ((4, 1 << 24), "float32", 4 * (1 << 24) * 4)]
# three executions: a q12 (0..10 s), a q14 (10..20 s), a q19 (20..30 s)
TRACE = {"sync_s": 0.0, "device": {"/device:TPU:0": {"XLA Ops": [
    ("fusion.8", 1.0, 4.0), ("gather.2", 11.0, 2.0), ("gather.2", 21.0, 5.0)]}}}
RUNS = [
    {"template": "q12", "unix_start": 0.0, "unix_end": 10.0, "start": 0.0, "end": 10.0,
     "failed": False, "counters": {"device_join_batches": 58, "device_grouped_batches": 58,
                                   "join_priced_dispatch_rows": 1 << 20,
                                   "join_window_gathers": 58}},
    {"template": "q14", "unix_start": 10.0, "unix_end": 20.0, "start": 10.0, "end": 20.0,
     "failed": False, "counters": {"device_join_batches": 58, "device_stage_batches": 58,
                                   "join_priced_dispatch_rows": 1 << 20,
                                   "join_unwindowed_gathers": 58}},
    {"template": "q19", "unix_start": 20.0, "unix_end": 30.0, "start": 20.0, "end": 30.0,
     "failed": False, "counters": {"device_join_batches": 58, "device_stage_batches": 58,
                                   "join_priced_dispatch_rows": 1 << 20,
                                   "join_unwindowed_gathers": 58}},
]
SPANS = [("query", 0.0, 10.0), ("placement.decide", 0.1, 0.2),
         ("device.dispatch", 1.0, 2.0), ("join.gather", 1.1, 1.6), ("join.membership", 1.2, 1.3),
         ("device.launch", 1.6, 1.9),
         ("query", 10.0, 20.0), ("device.dispatch", 11.0, 11.5), ("join.gather", 11.1, 11.3),
         ("device.launch", 11.3, 11.4),
         ("query", 20.0, 30.0), ("device.dispatch", 21.0, 21.6), ("join.gather", 21.1, 21.4),
         ("join.membership", 21.15, 21.2), ("join.membership", 21.25, 21.3),
         ("device.launch", 21.4, 21.5)]


def ctx_of(trace=TRACE, runs=RUNS, spans=SPANS):
    import xtrace as tr

    return {"cell": CELL, "templates": TEMPLATES, "executions": runs, "spans": spans,
            "trace": trace, "busy": tr.busy_union(trace), "window": (0.0, 30.0), "to_trace": 0.0,
            "window_s": 30.0, "queries": suite("queries").TEMPLATES, "rows": ROWS,
            "peaks": {"hbm_bytes_per_s": 819e9}}


def _live(monkeypatch, arrays):
    import filteredjoinbytes

    monkeypatch.setattr(filteredjoinbytes, "live_arrays", lambda: arrays)


@pytest.mark.parametrize("name, want", [
    ("filteredjoin.priced_over_delivered", (1 << 20) / (LINEITEM / 58)),
    ("filteredjoin.unwindowed_gathers_per_query", 2 * 58 / 3),
    ("filteredjoin.batches_per_query", 58.0),
    ("filteredjoin.membership_ms", 1e3 * (0.1 + 0.05 + 0.05) / 3),
    ("filteredjoin.residency_misses", 0.0),
    ("filteredjoin.launch_ms", 1e3 * (0.3 + 0.1 + 0.1) / 3),
    ("filteredjoin.dispatch_host_ms", 1e3 * (1.0 + 0.5 + 0.6) / 3),
    ("filteredjoin.gather_ms", 1e3 * (0.5 + 0.2 + 0.3) / 3),
    ("filteredjoin.decide_ms", 1e3 * 0.1 / 3),
])
def test_the_readers_on_a_hand_made_window(name, want):
    assert reader(name).read(ctx_of()) == pytest.approx(want)


def test_what_the_parents_price_would_read():
    """A program that prices one bucket and dispatches eight (had it the
    counter): an eighth, less the tail."""
    held = [dict(r, counters=dict(r["counters"], join_priced_dispatch_rows=1 << 17))
            for r in RUNS]
    assert reader("filteredjoin.priced_over_delivered").read(ctx_of(runs=held)) \
        == pytest.approx((1 << 17) / (LINEITEM / 58))
    # an execution that ran its join on the host tier prices and delivers nothing
    host = [dict(RUNS[0], counters={}), RUNS[1]]
    assert reader("filteredjoin.priced_over_delivered").read(ctx_of(runs=host)) \
        == pytest.approx((1 << 20) / (LINEITEM / 58))


@pytest.mark.parametrize("twin, accepted", sorted(TWINS.items()))
def test_a_twin_reads_what_the_accepted_reader_reads(twin, accepted):
    """One arithmetic under two names (`benchmark/twin.py`)."""
    runs = [dict(r, counters=dict(r["counters"], hbm_cache_misses=k)) for k, r in enumerate(RUNS)]
    ctx = ctx_of(runs=runs)
    got = reader(twin).read(ctx)
    assert got is not None and got == reader(accepted).read(ctx)


def test_the_roofline_share_counts_a_dispatchs_planes_and_an_unordered_dimensions_pack(
        monkeypatch, capsys):
    """q12: three value planes, a code plane, an index plane, a gathered value
    and a membership plane of bytes; q14: three value planes, an index plane
    and ONE row of `part`'s pack whole; q19: three value planes, an index
    plane, two membership planes and THREE rows of the pack whole
    (benchmark/filteredjoinbytes.py), against the device's busy seconds
    inside each execution."""
    import filteredjoinbytes as fb

    queries = suite("queries").TEMPLATES
    row = (1 << 21) * 4
    assert fb.byte_plane_nbytes(ARRAYS) == 1 << 20
    assert fb.pack_row_nbytes(ARRAYS, PART) == row
    assert fb.pack_row_nbytes(ARRAYS, ORDERS) == (1 << 24) * 4
    assert fb.pack_row_nbytes(ARRAYS, 1 << 25) is None
    assert fb.dispatch_bytes(queries["q12"], ARRAYS, ROWS) == 6 * PLANE + (1 << 20)
    assert fb.dispatch_bytes(queries["q14"], ARRAYS, ROWS) == 4 * PLANE + row
    assert fb.dispatch_bytes(queries["q19"], ARRAYS, ROWS) == 4 * PLANE + 2 * (1 << 20) + 3 * row
    assert fb.dispatch_bytes({"program": None, "tables": ()}, ARRAYS, ROWS) is None
    no_pack = [a for a in ARRAYS if len(a[0]) == 1]
    assert fb.dispatch_bytes(queries["q19"], no_pack, ROWS) is None    # not resident
    assert fb.dispatch_bytes(queries["q12"], no_pack, ROWS) == 6 * PLANE + (1 << 20)
    _live(monkeypatch, ARRAYS)
    share = reader("filteredjoin.join_hbm_share").read(ctx_of())
    least_bytes = 58 * (14 * PLANE + 3 * (1 << 20) + 4 * row)
    assert share == pytest.approx(100.0 * least_bytes / 819e9 / (4.0 + 2.0 + 5.0))
    assert 0 < share < 100
    said = [json.loads(x) for x in capsys.readouterr().out.splitlines()][-1]
    assert said["phase"] == "roofline" and said["filtered_join_least_bytes"] == least_bytes
    assert said["bound"] == "hbm"


def test_the_roofline_share_cannot_pass_100_percent(monkeypatch):
    """The chip cannot read the planes and the pack faster than its HBM gives
    them: with the device busy exactly as long as the least time, 100%."""
    import filteredjoinbytes as fb

    _live(monkeypatch, ARRAYS)
    queries = suite("queries").TEMPLATES
    q19_s = 58 * fb.dispatch_bytes(queries["q19"], ARRAYS, ROWS) / 819e9
    exact = {"sync_s": 0.0, "device": {"/device:TPU:0": {"XLA Ops": [("gather.2", 21.0, q19_s)]}}}
    rd = reader("filteredjoin.join_hbm_share")
    assert rd.read(ctx_of(exact, [RUNS[2]])) == pytest.approx(100.0)
    half = {"sync_s": 0.0, "device": {"/device:TPU:0": {"XLA Ops": [("gather.2", 21.0, 2 * q19_s)]}}}
    assert rd.read(ctx_of(half, [RUNS[2]])) == pytest.approx(50.0)
    assert rd.read(ctx_of(exact, [dict(RUNS[2], failed=True)])) is None
    assert rd.read(dict(ctx_of(exact, [RUNS[2]]), busy=[])) is None
    _live(monkeypatch, [])
    assert rd.read(ctx_of(exact, [RUNS[2]])) is None


def test_the_readers_on_the_recorded_chip_trace(monkeypatch):
    """The recorded second of a v5e's trace (PR 24) with this cell's
    executions laid over its window: the roofline share divides by the busy
    seconds the trace holds inside them, the span and counter readers read
    the executions as they are."""
    import xtrace as tr

    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        rec = json.load(f)
    trace, (lo, hi) = rec["trace"], rec["window"]
    trace["device"] = {p: {ln: [tuple(e) for e in ev] for ln, ev in lines.items()}
                       for p, lines in trace["device"].items()}
    mid = (lo + hi) / 2
    runs = [dict(RUNS[1], unix_start=lo, unix_end=mid, start=0.0, end=mid - lo),
            dict(RUNS[2], unix_start=mid, unix_end=hi, start=mid - lo, end=hi - lo)]
    ctx = dict(ctx_of(trace, runs, [tuple(s) for s in rec["spans"]]), window=(lo, hi),
               window_s=hi - lo)
    _live(monkeypatch, ARRAYS)
    busy = tr.busy_in(ctx["busy"], (lo, hi))
    assert busy == pytest.approx(rec["expect"]["busy_s"], rel=1e-6)
    row = (1 << 21) * 4
    least = 58 * (8 * PLANE + 2 * (1 << 20) + 4 * row) / 819e9
    assert reader("filteredjoin.join_hbm_share").read(ctx) == pytest.approx(100.0 * least / busy)
    assert reader("filteredjoin.batches_per_query").read(ctx) == 58.0
    assert reader("filteredjoin.unwindowed_gathers_per_query").read(ctx) == 58.0
    assert reader("filteredjoin.priced_over_delivered").read(ctx) \
        == pytest.approx((1 << 20) / (LINEITEM / 58))
    assert reader("filteredjoin.membership_ms").read(ctx) is None    # PR 24's program had no such span


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_counters_and_the_span_gives_nothing_to_read(name, monkeypatch):
    """The parent's program on the host tier: no `join_priced_dispatch_rows`,
    no `join_unwindowed_gathers`, no `join.membership` span, no join
    dispatched on the device: None or the accepted reader's own reading, never
    a raise, so the result line leaves the metric out."""
    _live(monkeypatch, ARRAYS)
    runs = [dict(r, counters={}) for r in RUNS]
    spans = [s for s in SPANS if s[0] in ("query",)]
    got = reader(name).read(ctx_of(TRACE, runs, spans))
    if name == "filteredjoin.residency_misses":
        assert got == 0.0       # the accepted reader counts what the executions counted: none
    elif name == "filteredjoin.decide_ms":
        assert got == 0.0       # a span tree without a decision
    else:
        assert got is None


def test_the_list_less_readers_read_the_cells_window_true():
    """100% of the queries on the device, 58 dispatches each, nothing
    uploaded."""
    ctx = ctx_of()
    assert reader("placement.device_query_share").read(ctx) == 100.0
    assert reader("stages.dispatches_per_query").read(ctx) == 58.0
    assert reader("h2d.bytes_per_query").read(ctx) == 0
