"""Set-up's layers (PR 36): the nine `setup.*` readers on a hand-made window,
their entries in `BENCHMARK.json` held by NAME, `coldreport.py` end to end at
a test's size, and, by name too, what the two tests of `test_bench_adhoc.py`
that count entries from the end of a list held of PR 32's and PR 34's
entries (`tests/conftest.py` marks those as expected to fail: ROADMAP M6).
On the CPU: nothing here is a measurement."""

import json
import os

import pytest

import coldreport
import run
import setup_counters
from bench_helpers import BENCH, REPO, add_cell

CELLS = ["tpch_sf10.scanagg", "tpch_sf1.joins", "tpch_sf1.parquet_scan",
         "tpch_sf30_mesh4.scanagg", "tpch_sf10.adhoc_scanagg"]
# name -> (unit, layer)
SETUP_METRICS = {
    "setup.query_s": ("s", "Set-up"),
    "setup.plane_prepare_s": ("s", "h2d, residency"),
    "setup.plane_put_s": ("s", "h2d, residency"),
    "setup.dict_encode_s": ("s", "h2d, residency"),
    "setup.program_build_s": ("s", "Compile"),
    "setup.calibrate_s": ("s", "Placement"),
    "setup.unnamed_share": ("%", "Set-up"),
    "setup.content_hash_s": ("s", "h2d, residency"),
    "setup.residency_build_s": ("s", "h2d, residency")}
# what the process has counted, in microseconds
TOTAL = {"query_wall_us": 40_000_000, "h2d_upload_us": 9_000_000, "h2d_prepare_us": 6_000_000,
         "dict_encode_us": 3_000_000, "content_hash_us": 8_000_000, "calibrate_us": 500_000,
         "residency_build_us": 1_500_000,
         "jax_trace_us": 1_000_000, "jax_lower_us": 600_000, "xla_compile_us": 400_000,
         "hbm_h2d_bytes": 7}
# two executions of a window: the second encoded a fresh slice and uploaded a morsel
RUNS = [{"template": "q1", "counters": {"query_wall_us": 4_000_000}},
        {"template": "q19", "counters": {"query_wall_us": 6_000_000, "dict_encode_us": 1_000_000,
                                         "h2d_upload_us": 1_000_000, "h2d_prepare_us": 500_000,
                                         "residency_build_us": 500_000}}]
# before the window: 30 s in queries; 8 upload (5.5 prepare), 2 encode, 8 hash, 1 residency
# builds, 0.5 calibration, 2 program build
EXPECTED = {"setup.query_s": 30.0, "setup.plane_prepare_s": 5.5, "setup.plane_put_s": 2.5,
            "setup.dict_encode_s": 2.0, "setup.program_build_s": 2.0, "setup.calibrate_s": 0.5,
            "setup.unnamed_share": 100.0 * (30.0 - 8.0 - 2.0 - 8.0 - 1.0 - 0.5 - 2.0) / 30.0,
            "setup.content_hash_s": 8.0, "setup.residency_build_s": 1.0}
WHOLE = {"setup.query_s": 40.0, "setup.plane_prepare_s": 6.0, "setup.plane_put_s": 3.0,
         "setup.dict_encode_s": 3.0, "setup.program_build_s": 2.0, "setup.calibrate_s": 0.5,
         "setup.unnamed_share": 100.0 * (40.0 - 9.0 - 3.0 - 8.0 - 1.5 - 0.5 - 2.0) / 40.0,
         "setup.content_hash_s": 8.0, "setup.residency_build_s": 1.5}
# the counters each reader needs: without one of them it has nothing to read
NEEDS = {"setup.query_s": ["query_wall_us"], "setup.plane_prepare_s": ["h2d_prepare_us"],
         "setup.plane_put_s": ["h2d_upload_us", "h2d_prepare_us"],
         "setup.dict_encode_s": ["dict_encode_us"],
         "setup.program_build_s": ["jax_trace_us", "jax_lower_us", "xla_compile_us"],
         "setup.calibrate_s": ["calibrate_us"],
         "setup.content_hash_s": ["content_hash_us"],
         "setup.residency_build_s": ["residency_build_us"],
         "setup.unnamed_share": ["query_wall_us"] + list(setup_counters.NAMED)}


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def snapshot_of(monkeypatch, total):
    from daft_tpu.ops import counters

    monkeypatch.setattr(counters, "snapshot", lambda: dict(total))


@pytest.mark.parametrize("name", list(SETUP_METRICS))
def test_a_reader_takes_the_process_total_less_the_window(monkeypatch, name):
    snapshot_of(monkeypatch, TOTAL)
    assert reader(name).read({"executions": RUNS}) == pytest.approx(EXPECTED[name])
    # an empty window: everything the process counted is set-up
    assert reader(name).read({"executions": []}) == pytest.approx(WHOLE[name])


@pytest.mark.parametrize("name", list(SETUP_METRICS))
def test_a_reader_gives_none_on_a_program_without_its_counter(monkeypatch, name):
    for missing in NEEDS[name]:
        snapshot_of(monkeypatch, {k: v for k, v in TOTAL.items() if k != missing})
        assert reader(name).read({"executions": RUNS}) is None, missing
    # the parent of PR 36 has the two first-touch counters and no other
    snapshot_of(monkeypatch, {k: TOTAL[k] for k in ("h2d_upload_us", "dict_encode_us")})
    got = reader(name).read({"executions": RUNS})
    assert (got is None) == (name != "setup.dict_encode_s")


def test_the_unnamed_share_reads_as_it_reads(monkeypatch):
    read = reader("setup.unnamed_share").read
    # counters summed over pool threads can exceed the queries' wall time
    snapshot_of(monkeypatch, dict(TOTAL, dict_encode_us=60_000_000))
    assert read({"executions": []}) < 0
    # no query before the window: no whole to take a share of
    snapshot_of(monkeypatch, dict(TOTAL, query_wall_us=10_000_000))
    assert read({"executions": RUNS}) is None
    # the first-touch sum keeps its reading beside its three parts
    snapshot_of(monkeypatch, TOTAL)
    parts = sum(reader(n).read({"executions": RUNS}) for n in (
        "setup.plane_prepare_s", "setup.plane_put_s", "setup.dict_encode_s"))
    assert reader("setup.first_touch_s").read({"executions": RUNS}) == pytest.approx(parts)


def test_the_named_counters_are_the_programs_and_disjoint():
    from daft_tpu.observability.metrics import DECLARED_COUNTERS

    assert set(setup_counters.COLD) <= set(DECLARED_COUNTERS)
    assert len(set(setup_counters.NAMED)) == len(setup_counters.NAMED)
    # prepare lies inside upload: counted there, never beside it
    assert "h2d_prepare_us" not in setup_counters.NAMED and "h2d_upload_us" in setup_counters.NAMED
    assert setup_counters.WALL not in setup_counters.NAMED
    assert set(setup_counters.BUILD) <= set(setup_counters.NAMED)


# ---- the entries, by name ---------------------------------------------------------------

def entries():
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    return spec, {m["name"]: m for m in spec["per_layer"]}


@pytest.mark.parametrize("name", list(SETUP_METRICS))
def test_a_setup_entry_names_the_five_cells(name):
    spec, by_name = entries()
    unit, layer = SETUP_METRICS[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter",
        "layer": layer, "moves": "setup_s", "workloads": CELLS}
    assert set(CELLS) <= {w["name"] for w in spec["workloads"]}
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # appended: after every entry the parent had, whatever follows later
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index(name) > names.index("literals.query_p95_ms")
    # a layer BENCHMARK.json named before, or the one PERF.md §3 gained
    older = {m["layer"] for m in spec["per_layer"] if not m["name"].startswith("setup.")
             or m["name"] == "setup.first_touch_s"}
    assert layer in older | {"Set-up"}


def test_first_touch_keeps_its_entry_and_its_list():
    _spec, by_name = entries()
    assert by_name["setup.first_touch_s"]["workloads"] == ["tpch_sf10.scanagg", "tpch_sf1.joins"]
    assert by_name["setup.first_touch_s"]["layer"] == "h2d, residency"


def test_every_cell_reports_the_nine():
    for name in CELLS:
        reported = {m["name"] for m in run.Cell(REPO, name).metrics("per_layer")}
        assert set(SETUP_METRICS) <= reported


def test_the_adhoc_cells_entries_are_as_they_were():
    """`test_bench_adhoc.py::test_the_entries_stand_at_the_end_of_their_lists`,
    without its counting from the end."""
    import test_bench_adhoc as adhoc

    spec, by_name = entries()
    names = [m["name"] for m in spec["per_layer"]]
    mine = [m for m in spec["per_layer"] if m["name"].startswith("literals.")]
    assert [m["name"] for m in mine] == adhoc.LITERAL_METRICS
    at = names.index(adhoc.LITERAL_METRICS[0])
    assert names[at:at + 13] == adhoc.LITERAL_METRICS  # together, in their order
    assert all(n.startswith("mesh.") for n in names[at - 5:at])
    assert all(m["workloads"] == [adhoc.CELL] for m in mine)
    assert [m["layer"] for m in mine[:5]] == ["Device stages"] * 4 + ["Kernels"]
    assert [m["moves"] for m in mine[:5]] == ["setup_s"] + ["query_ms.geomean"] * 4
    for twin, accepted in adhoc.TWINS.items():
        assert {k: by_name[twin][k] for k in ("unit", "better", "source", "layer", "moves")} \
            == {k: by_name[accepted][k] for k in ("unit", "better", "source", "layer", "moves")}
        assert adhoc.CELL not in by_name[accepted].get("workloads", [adhoc.CELL])
    reported = {m["name"] for m in run.Cell(REPO, adhoc.CELL).metrics("per_layer")}
    assert reported == set(adhoc.LITERAL_METRICS) | set(SETUP_METRICS) | {
        "placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
        "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"}
    # no list that was there took the cell
    assert all(adhoc.CELL not in m.get("workloads", [])
               for m in spec["per_layer"] + spec["end_to_end"]
               if not m["name"].startswith("literals.") and m["name"] not in SETUP_METRICS)


def test_the_four_chip_cells_entries_are_as_they_were():
    """`test_bench_adhoc.py`'s test of that name, without its counting from
    the end."""
    spec, _by_name = entries()
    mesh_cell = "tpch_sf30_mesh4.scanagg"
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [mesh_cell]
    assert four[0]["config"] == "tpch-sf30-4chip" and four[0]["traffic"] == "scanagg_mesh"
    mesh = [m for m in spec["per_layer"] if m["name"].startswith("mesh.")]
    assert [m["name"] for m in mesh] == [
        "mesh.shards_per_dispatch", "mesh.launch_ms", "mesh.shard_skew_share",
        "mesh.collective_share", "mesh.scan_hbm_share"]
    assert all(m["workloads"] == [mesh_cell] and m["layer"] == "Mesh" for m in mesh)
    reported = {m["name"] for m in run.Cell(REPO, mesh_cell).metrics("per_layer")}
    assert reported == {m["name"] for m in mesh} | set(SETUP_METRICS) | {
        "placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
        "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"}


# ---- coldreport.py ----------------------------------------------------------------------

def test_coldreport_end_to_end_at_a_tests_size(bench_root, capsys):
    """The set-up of a cell as a timeline: the load and each template's two
    warm-up passes, by span, with the cold counters' deltas. Under the default
    configuration on the CPU every query runs on the host tier."""
    add_cell(bench_root, "tiny.scan", "tiny", "scanagg")
    rep = coldreport.report(bench_root, "tiny.scan", seed=2**31 + 36, require_tpu=False)
    assert json.loads(json.dumps(rep)) == rep
    assert rep["dropped"] == 0 and rep["spans"] > 0 and rep["device"]["platform"] == "cpu"
    assert [p["phase"] for p in rep["phases"]] == [
        "load", "q1.pass1", "q1.pass2", "q6.pass1", "q6.pass2"]
    load = rep["phases"][0]
    assert "load.from_arrow" in load["self_ms"]
    for p in rep["phases"]:
        assert p["wall_s"] > 0 and p["device_busy_s"] is None  # the CPU: no device plane
        assert 0 <= p["unnamed_s"] <= p["wall_s"]
        assert sum(p["self_ms"].values()) / 1e3 <= p["wall_s"] + 1e-6
        assert set(p["counters"]) <= set(setup_counters.COLD)
        assert p["calibrate_build_s"] >= 0
    for p in rep["phases"][1:]:
        # a query: the root is open, and the query counted its own wall time
        assert "query" in p["self_ms"] and 0 < p["counters"]["query_wall_us"] <= p["wall_s"]
        named = sum(ms for n, ms in p["self_ms"].items() if n != "query") / 1e3
        assert p["unnamed_s"] == pytest.approx(p["wall_s"] - named, abs=1e-6)
    # the harness's line of the device came first, the report is the run's to print
    assert '"phase": "device"' in capsys.readouterr().out


def test_coldreport_refuses_a_machine_without_a_tpu(bench_root):
    add_cell(bench_root, "tiny.scan", "tiny", "scanagg")
    with pytest.raises(run.HarnessError, match="no TPU"):
        coldreport.report(bench_root, "tiny.scan", seed=1)
