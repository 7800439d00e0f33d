"""The harness end to end on the CPU at a size a test can hold: a cell is
added as files, the control comes out as not correct, a broken timed path
comes out as not correct, and a run that cannot be a measurement is refused.

These skip the harness's look for a chip (`require_tpu=False`); under the
default configuration on the CPU every query runs on the host tier. Nothing
they print is a measurement.
"""

import json
import os
import subprocess
import sys

import pytest

import compare
import run
from bench_helpers import REPO, add_cell


def write(path, text):
    with open(path, "w") as f:
        f.write(text)


def test_a_cell_is_added_as_files_and_nothing_there_is_edited(bench_root):
    """What a later PR does: a configuration, a traffic mix, a template suite
    with its reference and generator, a per-layer metric and one entry each in
    BENCHMARK.json; no file that was there changes."""
    bench = os.path.join(bench_root, "benchmark")
    before = {p: open(os.path.join(bench, p), "rb").read()
              for p in ("run.py", "arith.py", "xtrace.py", "compare.py",
                        "queries/tpch.py", "traffic/scanagg.json")}
    # a new suite: one more template over the same generator and reference
    write(os.path.join(bench, "queries", "tpch_more.py"),
          "import importlib.util, os\n"
          "_s = importlib.util.spec_from_file_location('t', os.path.join("
          "os.path.dirname(__file__), 'tpch.py'))\n"
          "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
          "from daft_tpu import col\n"
          "def count_lines(t):\n"
          "    return t['lineitem'].agg(col('l_orderkey').count().alias('n'))\n"
          "TEMPLATES = dict(_m.TEMPLATES, count_lines={'program': count_lines,"
          " 'tables': ('lineitem',)})\n")
    write(os.path.join(bench, "reference", "tpch_more.py"),
          "import importlib.util, os\n"
          "_s = importlib.util.spec_from_file_location('r', os.path.join("
          "os.path.dirname(__file__), 'tpch.py'))\n"
          "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
          "to_bfloat16 = _m.to_bfloat16\n"
          "def answer(template, tables, storage=None):\n"
          "    if template == 'count_lines':\n"
          "        return {'n': [tables['lineitem'].num_rows]}\n"
          "    return _m.answer(template, tables, storage)\n")
    write(os.path.join(bench, "datagen", "tpch_more.py"),
          open(os.path.join(bench, "datagen", "tpch.py")).read())
    write(os.path.join(bench, "traffic", "count_and_q6.json"), json.dumps(
        {"suite": "tpch_more", "loop": "closed", "clients": 1,
         "templates": ["count_lines", "q6"], "trace_seconds": 0.2}))
    spec = add_cell(bench_root, "tiny.count", "tiny", "count_and_q6",
                    float_rel_limit={"count_lines": 0, "q6": 1e-6})
    write(os.path.join(bench, "layer_metrics", "test.executions.py"),
          "def read(ctx):\n    return len(ctx['executions'])\n")
    spec["per_layer"].append({"name": "test.executions", "unit": "count", "better": "higher",
                              "source": "program_counter", "layer": "Device",
                              "moves": "scan_rows_per_s", "workloads": ["tiny.count"]})
    write(os.path.join(bench_root, "BENCHMARK.json"), json.dumps(spec))

    result = run.run_cell(bench_root, "tiny.count", seed=2**31 + 7, seconds=0.3,
                          trace=False, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    # the cell reports the end-to-end metrics that list no cells, and not the one that does
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())

    cell = run.Cell(bench_root, "tiny.count")
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "test.executions" in names and "kernels.scan_hbm_share" not in names
    reader = run.load_module(os.path.join(bench, "layer_metrics", "test.executions.py"))
    assert reader.read({"executions": [1, 2, 3]}) == 3
    for p, content in before.items():
        assert open(os.path.join(bench, p), "rb").read() == content, p


@pytest.mark.parametrize("workload, traffic", [("tiny.scanagg", "scanagg"),
                                               ("tiny.joins", "joins")])
def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root, workload, traffic):
    add_cell(bench_root, workload, "tiny", traffic, scale_factor=0.02)
    result = run.run_cell(bench_root, workload, seed=2**31 + 3, seconds=0.5,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["metrics"]["query_ms.geomean"]["unit"] == "ms"
    assert result["metrics"]["scan_rows_per_s"]["value"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("workload", ["tpch_sf10.scanagg", "tpch_sf1.joins"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(workload, seed):
    """The reference with its float columns stored in bfloat16, put in the
    program's place, fails the float limit of the cell's own configuration in
    at least one of the cell's templates (at a test's scale; the chip readings
    at the cells' own scale are in PERF.md)."""
    cell = run.Cell(REPO, workload)
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = []
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts.append(compare.within(compare.compare(ref, low), lim))
    assert not all(verdicts), verdicts


@pytest.mark.parametrize("fault", ["float", "count", "raises"])
def test_a_broken_timed_path_is_not_correct(bench_root, monkeypatch, fault):
    """The rest of a run, with the answer altered where the timed path
    produces it: one float off by one part in a thousand, one count off by
    one, or the execution raising, in the window only."""
    add_cell(bench_root, "tiny.scanagg", "tiny", "scanagg", scale_factor=0.02)
    sound = run.execute
    calls = {"n": 0}

    def broken(fn, tables):
        calls["n"] += 1
        out = sound(fn, tables)
        if calls["n"] <= 4:  # the four warm-up executions stay sound
            return out
        if fault == "raises":
            raise RuntimeError("injected")
        if fault == "float" and "revenue" in out:
            out["revenue"][0] *= 1.001
        if fault == "count" and "count_order" in out:
            out["count_order"][0] += 1
        return out

    monkeypatch.setattr(run, "execute", broken)
    result = run.run_cell(bench_root, "tiny.scanagg", seed=11, seconds=0.5,
                          trace=False, require_tpu=False)
    assert result["correct"] is False
    assert 0 < result["failed"] <= result["attempted"]


def test_program_knobs_in_the_environment_are_refused():
    with pytest.raises(run.HarnessError, match="DAFT_TPU_DEVICE"):
        run.refuse_program_knobs({"PATH": "/bin", "DAFT_TPU_DEVICE": "on"})
    run.refuse_program_knobs({"PATH": "/bin", "BENCH_RUN": "3"})


def test_a_cpu_backend_is_refused_and_named():
    peaks = run.load_json(os.path.join(REPO, "benchmark", "peaks.json"))
    with pytest.raises(run.HarnessError, match="cpu"):
        run.find_device(1, peaks)


def test_a_device_kind_outside_the_peaks_table_is_refused(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(run.HarnessError, match="TPU v9 imaginary"):
        run.find_device(1, {"TPU v5 lite": {}})
    with pytest.raises(run.HarnessError, match="4 chips"):
        run.find_device(4, {"TPU v9 imaginary": {}})


def test_the_command_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = {k: v for k, v in os.environ.items() if not k.startswith("DAFT_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
         "tpch_sf1.joins", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "cpu" in out.stdout and "FAILED" in out.stdout
    last = out.stdout.strip().splitlines()[-1]
    assert not last.startswith("{")
    env["DAFT_TPU_DEVICE"] = "on"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
         "tpch_sf1.joins", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and "DAFT_TPU_DEVICE" in out.stdout
