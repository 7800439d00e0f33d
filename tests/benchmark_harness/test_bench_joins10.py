"""The `tpch_joins10` suite (`tpch_sf10.joins`): the cell's traffic is correct
through the harness at a test's size; the reference's Q10 and Q14 against
tables of a few rows worked by hand; the bfloat16 control is not correct; the
suite refuses a program without the fused TopN's counters and one whose q3 or
q10 did not ride it over more than one batch; and the `jointopn.*` readers on
hand-made executions and a hand-made trace (a roofline share that cannot pass
100%), each None where there is nothing to read. On the CPU: nothing here is
a measurement."""

import datetime
import os

import pyarrow as pa
import pytest

import compare
import run
from bench_helpers import BENCH, REPO, add_cell

CELL = "tpch_sf10.joins"
METRICS = ["jointopn.batches_per_query", "jointopn.fetched_rows_per_query",
           "jointopn.select_ms", "jointopn.finalize_ms", "jointopn.dispatch_host_ms",
           "jointopn.launch_ms", "jointopn.codes_ms", "jointopn.index_ms",
           "jointopn.gather_ms", "jointopn.residency_misses", "jointopn.join_hbm_share"]
TWINS = {"jointopn.finalize_ms": "stages.finalize_ms",
         "jointopn.dispatch_host_ms": "stages.dispatch_host_ms",
         "jointopn.launch_ms": "stages.launch_ms", "jointopn.codes_ms": "join.codes_ms",
         "jointopn.index_ms": "join.index_ms", "jointopn.gather_ms": "join.gather_ms",
         "jointopn.residency_misses": "residency.misses_per_query"}


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def suite(kind):
    return run.load_module(os.path.join(BENCH, kind, "tpch_joins10.py"))


# ---- the entries and the configuration ---------------------------------------------------

def test_the_cell_and_its_metrics_are_appended_entries():
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    assert by_name[CELL] == dict(by_name[CELL], config="tpch-sf10-joins-1chip",
                                 traffic="joins_sf10", chips=1)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    cell = run.Cell(REPO, CELL)
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    mine = [m for m in spec["per_layer"] if m["name"].startswith("jointopn.")]
    assert [m["name"] for m in mine] == METRICS
    assert all(m["workloads"] == [CELL] for m in mine)
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for new, old in TWINS.items():   # a twin keeps its reader's layer, unit and direction
        assert {k: per_layer[new][k] for k in ("unit", "better", "source", "layer", "moves")} \
            == {k: per_layer[old][k] for k in ("unit", "better", "source", "layer", "moves")}
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert {"placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
            "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"} <= reported
    assert "join.codes_ms" not in reported and "kernels.scan_hbm_share" not in reported


def test_the_configuration_states_the_deployment():
    cfg = run.Cell(REPO, CELL).config
    sf1 = run.load_json(os.path.join(BENCH, "configs", "tpch-sf1-1chip.json"))
    assert cfg["suite"] == "tpch_joins10" and cfg["scale_factor"] == 10 and cfg["chips"] == 1
    assert cfg["source_scale_factor"] == 100 and list(cfg["reduced"]) == ["scale_factor"]
    # the guarantees of tpch-sf1-1chip, word for word but for the reference's file
    assert cfg["guarantees"]["exact"] == sf1["guarantees"]["exact"]
    assert cfg["guarantees"]["floats"] == sf1["guarantees"]["floats"]
    assert cfg["guarantees"]["answers"] == sf1["guarantees"]["answers"].replace(
        "reference/tpch.py", "reference/tpch_joins10.py")
    assert set(cfg["float_rel_limit"]) == {"q3", "q5", "q10", "q14"}
    for clause in ("2.4.3", "2.4.5", "2.4.10", "2.4.14", "4.1.3.1"):
        assert clause in cfg["source"]
    traffic = run.load_json(os.path.join(BENCH, "traffic", "joins_sf10.json"))
    # q14 is the suite's and not the traffic's: `auto` flipped on it between runs (PR 38)
    assert traffic["templates"] == ["q3", "q5", "q10"] and traffic["clients"] == 1
    assert "q14" in traffic["why"]
    assert traffic["suite"] == "tpch_joins10" and traffic["trace_seconds"] == 6


def test_q3_and_q5_are_the_join_cells_own():
    queries, plain = suite("queries"), run.load_module(os.path.join(BENCH, "queries", "tpch.py"))
    assert set(queries.TEMPLATES) == {"q3", "q5", "q10", "q14"}
    for name in ("q3", "q5"):
        assert queries.TEMPLATES[name]["tables"] == plain.TEMPLATES[name]["tables"]
    assert suite("reference").TEMPLATES["q3"].__code__ is not None
    tables = ["region", "nation", "customer", "orders", "lineitem", "supplier"]
    arrow = suite("datagen").generate(0.002, 5, tables)
    plain_ref = run.load_module(os.path.join(BENCH, "reference", "tpch.py"))
    for name in ("q3", "q5"):
        assert suite("reference").answer(name, arrow) == plain_ref.answer(name, arrow)


# ---- the reference's new queries, by hand --------------------------------------------------

def _date(y, m, d):
    return datetime.date(y, m, d)


def hand_tables():
    """Three customers, five orders, eight lines, four parts: small enough to
    work Q10 and Q14 on paper."""
    S = pa.large_string()
    return {
        "nation": pa.table({"n_nationkey": pa.array([0, 1], pa.int64()),
                            "n_name": pa.array(["ALGERIA", "PERU"], S)}),
        "customer": pa.table({
            "c_custkey": pa.array([10, 20, 30], pa.int64()),
            "c_name": pa.array(["ten", "twenty", "thirty"], S),
            "c_address": pa.array(["a10", "a20", "a30"], S),
            "c_nationkey": pa.array([1, 0, 1], pa.int64()),
            "c_phone": pa.array(["p10", "p20", "p30"], S),
            "c_acctbal": pa.array([1.5, -2.25, 300.0]),
            "c_comment": pa.array(["c10", "c20", "c30"], S)}),
        "orders": pa.table({
            "o_orderkey": pa.array([1, 2, 3, 4, 5], pa.int64()),
            "o_custkey": pa.array([10, 20, 10, 30, 20], pa.int64()),
            # order 4 is a day too early, order 5 a day too late
            "o_orderdate": pa.array([_date(1993, 10, 1), _date(1993, 11, 15), _date(1993, 12, 31),
                                     _date(1993, 9, 30), _date(1994, 1, 1)], pa.date32())}),
        "lineitem": pa.table({
            "l_orderkey": pa.array([1, 1, 2, 3, 3, 4, 5, 2], pa.int64()),
            "l_partkey": pa.array([1, 2, 3, 4, 1, 2, 3, 4], pa.int64()),
            "l_extendedprice": pa.array([100.0, 200.0, 400.0, 50.0, 1000.0, 70.0, 90.0, 10.0]),
            "l_discount": pa.array([0.0, 0.5, 0.25, 0.0, 0.1, 0.0, 0.0, 0.0]),
            # line 5 (order 3) was not returned
            "l_returnflag": pa.array(["R", "R", "R", "R", "N", "R", "R", "R"], S),
            "l_shipdate": pa.array([_date(1995, 9, 1), _date(1995, 9, 30), _date(1995, 10, 1),
                                    _date(1995, 8, 31), _date(1995, 9, 15), _date(1995, 9, 2),
                                    _date(1996, 9, 2), _date(1995, 9, 3)], pa.date32())}),
        "part": pa.table({"p_partkey": pa.array([1, 2, 3, 4], pa.int64()),
                          "p_type": pa.array(["PROMO BRUSHED TIN", "STANDARD PROMO TIN",
                                              "PROMO PLATED STEEL", "SMALL BRASS"], S)}),
    }


def test_q10_by_hand():
    got = suite("reference").answer("q10", hand_tables())
    # customer 10: order 1 (100 + 200 * 0.5 = 200) and order 3's returned line (50) = 250;
    # customer 20: order 2 (400 * 0.75 + 10 = 310); customer 30's order is out of the quarter
    assert got == {"c_custkey": [20, 10], "c_name": ["twenty", "ten"],
                   "revenue": [310.0, 250.0], "c_acctbal": [-2.25, 1.5],
                   "n_name": ["ALGERIA", "PERU"], "c_address": ["a20", "a10"],
                   "c_phone": ["p20", "p10"], "c_comment": ["c20", "c10"]}


def test_q10_breaks_a_revenue_tie_by_the_key_and_keeps_twenty():
    t = hand_tables()
    n = 25
    t["customer"] = pa.table({
        "c_custkey": pa.array(range(100, 100 + n), pa.int64()),
        "c_name": pa.array([f"n{i}" for i in range(n)], pa.large_string()),
        "c_address": pa.array(["a"] * n, pa.large_string()),
        "c_nationkey": pa.array([0] * n, pa.int64()),
        "c_phone": pa.array(["p"] * n, pa.large_string()),
        "c_acctbal": pa.array([0.0] * n), "c_comment": pa.array(["c"] * n, pa.large_string())})
    t["orders"] = pa.table({
        "o_orderkey": pa.array(range(n), pa.int64()),
        "o_custkey": pa.array(range(100 + n - 1, 99, -1), pa.int64()),
        "o_orderdate": pa.array([_date(1993, 11, 1)] * n, pa.date32())})
    t["lineitem"] = pa.table({
        "l_orderkey": pa.array(range(n), pa.int64()), "l_partkey": pa.array([1] * n, pa.int64()),
        "l_extendedprice": pa.array([5.0 if i % 2 else 7.0 for i in range(n)]),
        "l_discount": pa.array([0.0] * n),
        "l_returnflag": pa.array(["R"] * n, pa.large_string()),
        "l_shipdate": pa.array([_date(1995, 9, 1)] * n, pa.date32())})
    got = suite("reference").answer("q10", t)
    assert len(got["c_custkey"]) == 20
    # order i belongs to customer 124 - i: the sevens (even i) first, each tie by the key
    sevens = sorted(124 - i for i in range(n) if i % 2 == 0)
    fives = sorted(124 - i for i in range(n) if i % 2)
    assert got["c_custkey"] == (sevens + fives)[:20]
    assert got["revenue"] == [7.0] * 13 + [5.0] * 7


def test_q14_by_hand():
    got = suite("reference").answer("q14", hand_tables())
    # September 1995 ships lines 1 (part 1, 100), 2 (part 2, 100), 5 (part 1, 900),
    # 6 (part 2, 70) and 8 (part 4, 10): promotional are part 1's, 1000 of 1180
    assert got["promo_revenue"] == [pytest.approx(100.0 * 1000.0 / 1180.0, rel=1e-15)]
    empty = dict(hand_tables())
    empty["lineitem"] = empty["lineitem"].slice(0, 0)
    assert suite("reference").answer("q14", empty) == {"promo_revenue": [None]}


# ---- through the harness ---------------------------------------------------------------------

def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root):
    add_cell(bench_root, "tiny.joins10", "tiny", "joins_sf10", scale_factor=0.05,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    result = run.run_cell(bench_root, "tiny.joins10", seed=2**31 + 9, seconds=0.5,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """The reference with its float columns stored in bfloat16, put in the
    program's place, fails the cell's own float limits through q3 or q10."""
    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = {}
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts[name] = compare.within(compare.compare(ref, low), lim)
    assert not (verdicts["q3"] and verdicts["q10"]), verdicts


def test_the_program_answers_the_suite_on_the_device_tier():
    """The four templates forced onto the device at a test's size, morsels
    small enough that q3 and q10 take several batches: the reference's answers."""
    import daft_tpu as dt
    from daft_tpu.config import execution_config_ctx
    from daft_tpu.ops import counters

    cell = run.Cell(REPO, CELL)
    queries = suite("queries")   # a fresh module: nothing is checked off the TPU
    # the suite's four, q14 (which the traffic leaves out) among them
    arrow = cell.datagen.generate(0.01, 7, sorted(
        {t for tpl in queries.TEMPLATES.values() for t in tpl["tables"]}))
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    for name in queries.TEMPLATES:
        counters.reset()
        with execution_config_ctx(device_mode="on", morsel_size_rows=8192,
                                  pipeline_mode="force"):
            got = queries.TEMPLATES[name]["program"](tables).to_pydict()
        numbers = compare.compare(cell.reference.answer(name, arrow), got)
        assert compare.within(numbers, compare.limits(cell.config, name)), (name, numbers)
        assert counters.device_join_batches > 1, (name, counters.rejections)
        if name in ("q3", "q10"):
            assert counters.device_topn_runs == 1, (name, counters.rejections)
            assert counters.device_join_topn_batches == counters.device_join_batches
            assert counters.device_topn_fetched_rows == (10 if name == "q3" else 20)


# ---- the suite's own check -------------------------------------------------------------------

def test_a_program_without_the_counters_ends_the_run_at_import(monkeypatch, capsys):
    """The parent of the PR that added the cell: the suite exits 1 as it is
    imported, before any data is made, naming what is missing."""
    from daft_tpu.observability import metrics

    suite("queries")  # this program declares them
    monkeypatch.setattr(metrics, "DEVICE_COUNTER_NAMES", tuple(
        c for c in metrics.DEVICE_COUNTER_NAMES
        if c not in ("device_join_topn_batches", "device_topn_fetched_rows")))
    with pytest.raises(SystemExit) as e:
        suite("queries")
    assert e.value.code == 1
    out = capsys.readouterr()
    assert "device_join_topn_batches" in out.out and "device_topn_fetched_rows" in out.err
    with pytest.raises(SystemExit):
        run.Cell(REPO, CELL)  # the harness finds the cell's files first of all


@pytest.mark.parametrize("backend, deltas, ends", [
    # (runs, batches, fetched rows) of q3's and of q10's first execution
    ("cpu", [(0, 0, 0), (0, 0, 0)], None),          # tier-1 tests: nothing is checked
    ("tpu", [(1, 458, 10), (1, 458, 20)], None),    # the deployment
    ("tpu", [(1, 46, 7), (1, 46, 20)], None),       # fewer winners than the limit
    ("tpu", [(0, 0, 0), (1, 458, 20)], "q3"),       # q3 fell to per-batch tables
    ("tpu", [(1, 458, 10), (1, 1, 20)], "q10"),     # one batch is not the deployment
    ("tpu", [(1, 458, 10), (1, 458, 2048)], "q10"),  # a table fetched, not its winners
])
def test_q3_and_q10_must_ride_the_fused_topn(monkeypatch, capsys, backend, deltas, ends):
    import daft_tpu as dt
    import jax

    queries = suite("queries")  # a fresh module: a fresh count of builds
    arrow = suite("datagen").generate(0.002, 6, ["customer", "orders", "lineitem", "nation"])
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    base = (3, 100, 50)
    for name, delta in zip(("q3", "q10"), deltas):
        counts = iter([base, tuple(b + d for b, d in zip(base, delta))])
        monkeypatch.setattr(queries, "_topn_counts", lambda: next(counts))
        program = queries.TEMPLATES[name]["program"]
        program(tables)  # built, never executed
        if name == ends:
            with pytest.raises(SystemExit) as e:
                program(tables)
            assert e.value.code == 1
            out = capsys.readouterr()
            assert f"{name}'s first execution" in out.out and "fused TopN" in out.err
            return
        program(tables)
        program(tables)  # a third build checks nothing and reads no counter
    assert ends is None


# ---- the readers -----------------------------------------------------------------------------

# three executions: a q3 (0..10 s), a q14 (10..20 s), a q10 (20..30 s)
TRACE = {"sync_s": 0.0, "device": {"/device:TPU:0": {"XLA Ops": [
    ("while.5", 1.0, 4.0), ("fusion.3", 11.0, 2.0), ("while.7", 21.0, 5.0)]}}}
RUNS = [
    {"template": "q3", "unix_start": 0.0, "unix_end": 10.0, "start": 0.0, "end": 10.0,
     "failed": False, "counters": {"device_join_batches": 458, "device_grouped_batches": 458,
                                   "device_topn_runs": 1, "device_join_topn_batches": 458,
                                   "device_topn_fetched_rows": 10}},
    {"template": "q14", "unix_start": 10.0, "unix_end": 20.0, "start": 10.0, "end": 20.0,
     "failed": False, "counters": {"device_join_batches": 458, "device_stage_batches": 458}},
    {"template": "q10", "unix_start": 20.0, "unix_end": 30.0, "start": 20.0, "end": 30.0,
     "failed": False, "counters": {"device_join_batches": 458, "device_grouped_batches": 458,
                                   "device_topn_runs": 1, "device_join_topn_batches": 458,
                                   "device_topn_fetched_rows": 20}},
]
SPANS = [("query", 0.0, 10.0), ("stage.finalize", 8.0, 9.5), ("join.topn_select", 8.1, 9.1),
         ("device.d2h", 8.5, 9.0),
         ("query", 20.0, 30.0), ("stage.finalize", 27.0, 29.0), ("join.topn_select", 27.5, 28.0)]


def ctx_of(runs=RUNS, spans=SPANS):
    import xtrace as tr

    queries = suite("queries")
    return {"executions": runs, "spans": spans, "trace": TRACE, "busy": tr.busy_union(TRACE),
            "window": (0.0, 30.0), "to_trace": 0.0, "window_s": 30.0,
            "queries": queries.TEMPLATES, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_the_counter_readers():
    ctx = ctx_of()
    assert reader("jointopn.batches_per_query").read(ctx) == 458.0
    assert reader("jointopn.fetched_rows_per_query").read(ctx) == 15.0
    assert reader("jointopn.residency_misses").read(ctx) == 0.0
    # a program without the fused TopN (or the counters): nothing to read, no raise
    plain = [dict(r, counters={"device_join_batches": 458}) for r in RUNS]
    assert reader("jointopn.batches_per_query").read(ctx_of(plain)) is None
    assert reader("jointopn.fetched_rows_per_query").read(ctx_of(plain)) is None
    # runs that count no batches and no fetched rows are a program older than the counters
    older = [dict(r, counters={"device_topn_runs": 1}) for r in RUNS]
    assert reader("jointopn.batches_per_query").read(ctx_of(older)) is None
    assert reader("jointopn.fetched_rows_per_query").read(ctx_of(older)) is None


def test_the_span_readers():
    ctx = ctx_of()
    assert reader("jointopn.select_ms").read(ctx) == pytest.approx(1e3 * (1.0 + 0.5) / 2)
    assert reader("jointopn.select_ms").read(ctx_of(spans=[("query", 0.0, 10.0)])) is None
    # the twin is its accepted reader: finalize less the fetch inside it, per execution
    assert reader("jointopn.finalize_ms").read(ctx) \
        == reader("stages.finalize_ms").read(ctx) == pytest.approx(1e3 * (1.0 + 2.0) / 3)


def test_the_roofline_share_counts_a_dispatchs_planes(monkeypatch):
    import joinbytes

    queries = suite("queries")
    assert joinbytes.planes_per_dispatch(queries.TEMPLATES["q3"]) == 3 + 1 + 1
    assert joinbytes.planes_per_dispatch(queries.TEMPLATES["q5"]) == 2 + 2 + 3
    assert joinbytes.planes_per_dispatch({"program": None, "tables": ()}) is None
    plane = 131072 * 4
    arrays = [((131072,), "float32", plane)] * 9 + [((131072,), "bool", 131072)] * 20 \
        + [((16781312,), "float64", 16781312 * 8)] * 3
    assert joinbytes.dispatch_bytes(queries.TEMPLATES["q14"], arrays) == 5 * plane
    monkeypatch.setattr(joinbytes, "live_planes", lambda: arrays)
    share = reader("jointopn.join_hbm_share").read(ctx_of())
    least = 458 * (5 + 5 + 5) * plane / 819e9
    assert share == pytest.approx(100.0 * least / (4.0 + 2.0 + 5.0)) and 0 < share < 100
    monkeypatch.setattr(joinbytes, "live_planes", lambda: [])
    assert reader("jointopn.join_hbm_share").read(ctx_of()) is None
    monkeypatch.setattr(joinbytes, "live_planes", lambda: arrays)
    idle = dict(ctx_of(), busy=[])
    assert reader("jointopn.join_hbm_share").read(idle) is None
