"""Placement observability: CostBreakdown terms, the decision ledger,
per-query scopes under concurrency, recalibration cache invalidation,
calibration gauges, explain_placement / EXPLAIN PLACEMENT, QueryEnd
placements, the /api/placement endpoint, the calibrate tool, and the
zero-overhead guard (PR 6 discipline: a host query leaves the registry AND
the ledger untouched)."""

from __future__ import annotations

import json
import threading

import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability import placement
from daft_tpu.observability.metrics import registry
from daft_tpu.ops import costmodel


def _cal(rtt: float = 0.001) -> costmodel.Calibration:
    return costmodel.Calibration(
        rtt_s=rtt, h2d_bytes_per_s=1e9, d2h_bytes_per_s=2e6,
        mm_plane_rows_per_s=5e9, mm_cell_rate=5e10, scatter_rows_per_s=1e8,
        ext_cell_rate=5e9, host_agg_rate=1.5e8, host_factorize_rate=8e6,
        host_probe_rate=3e7)


# ---------------------------------------------------------------------------
# CostBreakdown: float-compatible totals + named terms
# ---------------------------------------------------------------------------

def test_cost_breakdown_terms_and_float_surface():
    cal = _cal(0.010)
    dev = costmodel.device_ungrouped_cost(cal, 1_000_000, 4_000_000, 2,
                                          coalesce=4.0, resident_bytes=8_000)
    assert set(dev.terms) == {"rtt", "h2d", "compute"}
    assert dev.terms["rtt"] == pytest.approx(0.010 / 4.0)
    assert dev.terms["h2d"] == pytest.approx(4_000_000 / 1e9)
    assert dev.total == pytest.approx(sum(dev.terms.values()))
    assert dev.notes["coalesce"] == 4.0
    assert dev.notes["residency_credit_s"] == pytest.approx(8_000 / 1e9)
    # float-compatible comparison/arithmetic (the decision-site contract)
    host = costmodel.host_agg_cost(cal, 1_000_000, 2, grouped=True,
                                   has_predicate=True)
    assert "factorize" in host.terms and "compute" in host.terms
    assert (dev < host) == (dev.total < host.total)
    assert dev * 1e3 == pytest.approx(dev.total * 1e3)
    assert float(dev) == dev.total
    assert (dev + 0.5).total == pytest.approx(dev.total + 0.5)
    d = dev.as_dict()
    assert d["total"] == pytest.approx(dev.total)
    assert d["note_residency_credit_s"] == pytest.approx(8_000 / 1e9)


def test_cost_breakdown_terms_cover_every_tier():
    cal = _cal()
    join = costmodel.device_join_agg_cost(cal, 100_000, 1_000_000, 3, 2, 1,
                                          0, 64, 4096, 100_000)
    assert {"rtt", "h2d", "compute", "d2h", "factorize"} <= set(join.terms)
    mesh = costmodel.over_mesh(costmodel.device_grouped_cost(
        cal, 1_000_000 // 8, 0, n_mm=4, n_ext=1, n_sct=0, cap=1024,
        factorize_rows=1_000_000), cal, 8, 1024 * 5 * 8)
    assert {"mesh_dispatch", "combine", "compute", "factorize"} <= set(mesh.terms)
    hj = costmodel.host_join_agg_cost(cal, 100_000, 3, 2, True, False)
    assert "probe" in hj.terms
    udf = costmodel.device_udf_cost(cal, 4096, 4096 * 1024, 1e9, 4096 * 512)
    assert {"rtt", "h2d", "compute", "d2h"} <= set(udf.terms)
    # add() folds into a named term in place
    before = join.terms["compute"]
    join.add("compute", 0.25)
    assert join.terms["compute"] == pytest.approx(before + 0.25)


# ---------------------------------------------------------------------------
# Ledger records, margins, rendering
# ---------------------------------------------------------------------------

def test_ledger_record_margin_and_render():
    led = placement.PlacementLedger(cap=16)
    cal = _cal(0.090)
    dev = costmodel.device_ungrouped_cost(cal, 200_000, 0, 1)
    host = costmodel.host_agg_cost(cal, 200_000, 1, grouped=False,
                                   has_predicate=True)
    rec = led.record("agg", "host", 200_000, device=dev, host=host,
                     detail="1 aggs, filtered")
    assert rec is not None
    m = rec.margin()
    assert m == pytest.approx(max(dev.total, host.total)
                              / min(dev.total, host.total))
    text = placement.render(led.records())
    assert "#1 agg" in text and "-> host" in text
    assert "rtt" in text and "margin:" in text and "TOTAL" in text
    # observation feeds back into the record and the render
    led.observe(rec, 0.5, term_seconds={"h2d": 0.1, "dispatch": 0.3},
                rows=400_000, dispatches=2)
    assert rec.observed["total"] == 0.5
    assert rec.error_ratio is not None
    assert "observed:" in placement.render(led.records())


def test_ledger_bounded_with_drop_counter():
    led = placement.PlacementLedger(cap=4)
    for i in range(10):
        led.record("agg", "host", i)
    st = led.stats()
    assert st["records"] == 4 and st["dropped"] == 6 and st["seq"] == 10
    # the newest records survive (FIFO eviction of the oldest)
    assert [r.rows for r in led.records()] == [6, 7, 8, 9]
    led_off = placement.PlacementLedger(cap=0)
    assert led_off.record("agg", "host", 1) is None
    assert led_off.stats()["records"] == 0


# ---------------------------------------------------------------------------
# Satellite: concurrent serving — no lost / cross-query-bled records
# ---------------------------------------------------------------------------

def test_concurrent_scopes_no_bleed_no_loss():
    """Hammer the ledger from N session threads, each inside its own
    query_scope: every scope must see exactly its own records (no
    cross-query bleed, none lost) and the process ledger stays bounded with
    an exact drop count — the SpanRecorder cap discipline."""
    led = placement.PlacementLedger(cap=64)
    N, M = 8, 40
    results = {}
    errors = []

    def worker(tid: int) -> None:
        try:
            with placement.query_scope(cap=M) as scope:
                for i in range(M):
                    led.record("agg", "host", rows=tid * 1000 + i,
                               detail=f"t{tid}")
                results[tid] = scope.to_dicts()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for tid in range(N):
        recs = results[tid]
        assert len(recs) == M, f"thread {tid} lost records"
        assert all(r["detail"] == f"t{tid}" for r in recs), "cross-query bleed"
        assert sorted(r["rows"] for r in recs) == [tid * 1000 + i
                                                  for i in range(M)]
    st = led.stats()
    assert st["records"] == 64
    assert st["dropped"] == N * M - 64
    assert st["seq"] == N * M


def test_scope_propagates_to_stage_threads():
    """Decision sites fire on pipeline stage threads; the scope must ride
    spawn_stage like the stats collector (a scope-less stage thread would
    silently drop the query's records)."""
    from daft_tpu.execution.pipeline import spawn_stage

    led = placement.ledger()
    with placement.query_scope() as scope:
        def gen():
            # runs on the spawned stage thread
            led.gate("agg", "stage-thread probe", 123, only_scoped=True)
            yield daft_tpu.from_pydict({"a": [1]})._materialize()[0]

        list(spawn_stage(gen()))
    recs = scope.to_dicts()
    assert len(recs) == 1 and recs[0]["reason"] == "stage-thread probe"


# ---------------------------------------------------------------------------
# Satellite: recalibration invalidates cached placement verdicts
# ---------------------------------------------------------------------------

def test_reset_calibration_invalidates_decision_caches():
    """Regression: reset_calibration() used to leave stale verdicts in the
    executor's decision/mesh-tier caches — a recalibrated process kept
    routing repeat shapes on prices from the discarded Calibration."""
    from daft_tpu.execution import executor

    executor._DECISION_CACHE.put(("stale", "join"), False)
    executor._MESH_TIER_CACHE.put(("stale", "mesh"), True)
    assert len(executor._DECISION_CACHE) and len(executor._MESH_TIER_CACHE)
    costmodel.reset_calibration()
    assert len(executor._DECISION_CACHE) == 0, \
        "stale join verdict survived recalibration"
    assert len(executor._MESH_TIER_CACHE) == 0, \
        "stale mesh verdict survived recalibration"


# ---------------------------------------------------------------------------
# Satellite: effective calibration exported as gauges
# ---------------------------------------------------------------------------

def test_calibration_terms_exported_as_gauges(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_COST_RTT", "0.042")
    monkeypatch.setenv("DAFT_TPU_COST_H2D", "2e9")
    monkeypatch.setenv("DAFT_TPU_COST_D2H", "3e6")
    # the mesh terms are live-probed like rtt/h2d when unset (r15) — pin
    # them so the gauge assertion is deterministic on any device count
    monkeypatch.setenv("DAFT_TPU_COST_ICI", "4.5e10")
    monkeypatch.setenv("DAFT_TPU_COST_MESH_DISPATCH", "2e-3")
    costmodel.reset_calibration()
    try:
        cal = costmodel.calibrate()
        assert cal.rtt_s == 0.042
        snap = registry().snapshot()
        assert snap["cost_rtt_s"] == 0.042
        assert snap["cost_h2d_bytes_per_s"] == 2e9
        assert snap["cost_d2h_bytes_per_s"] == 3e6
        assert snap["cost_ici_bytes_per_s"] == 4.5e10
        d = costmodel.calibration_dict()
        assert d["rtt_s"] == 0.042 and d["mm_cell_rate"] == 5e10
    finally:
        costmodel.reset_calibration()
    # reset zeroes the gauges (no stale terms after recalibration) and
    # calibration_dict reports un-calibrated honestly
    assert registry().snapshot()["cost_rtt_s"] == 0.0
    assert costmodel.calibration_dict() == {}


# ---------------------------------------------------------------------------
# Zero-overhead guard (PR 6 discipline)
# ---------------------------------------------------------------------------

def test_placement_zero_overhead_on_host_path():
    """A plain host query (no scope) must leave the process ledger AND the
    metrics registry untouched — placement observability can never tax the
    unobserved path. Covers BOTH common host routes: device_mode=off, and
    the default auto mode on a CPU backend where a large query crosses the
    min-rows AND backend gates (those are only_scoped — scope-less queries
    record nothing)."""
    led = placement.ledger()
    seq_before = led.stats()["seq"]
    before = registry().snapshot()
    df = daft_tpu.from_pydict({"a": list(range(1000)), "b": ["x", "y"] * 500})
    with execution_config_ctx(device_mode="off"):
        out = (df.where(col("a") >= 500)
               .groupby("b").agg(col("a").sum().alias("s")).to_pydict())
    assert len(out["b"]) == 2
    big = daft_tpu.from_pydict({"k": [i % 3 for i in range(80_000)],
                                "v": [float(i) for i in range(80_000)]})
    with execution_config_ctx(device_mode="auto", device_min_rows=1,
                              mesh_devices=1):
        big.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
    assert led.stats()["seq"] == seq_before, "ledger touched on host path"
    # the two queries counted their own wall time and nothing else
    assert set(registry().diff(before)) == {"query_wall_us"}, \
        "registry touched on host path"


# ---------------------------------------------------------------------------
# End to end: costed auto decision on a (simulated) accelerator backend
# ---------------------------------------------------------------------------

def test_explain_placement_costed_decision(monkeypatch):
    """The auto tier on a slow link (90ms round trip) cost-rejects a grouped agg to
    host; explain_placement must show BOTH per-term tables, the margin, and
    the host verdict — and the placement counters must attribute it."""
    import jax

    monkeypatch.setenv("DAFT_TPU_COST_RTT", "0.090")
    monkeypatch.setenv("DAFT_TPU_COST_H2D", "1e6")   # slow link: host wins
    monkeypatch.setenv("DAFT_TPU_COST_D2H", "1e6")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    costmodel.reset_calibration()
    before = registry().snapshot()
    try:
        df = daft_tpu.from_pydict({
            "k": [i % 7 for i in range(80_000)],
            "v": [float(i % 101) for i in range(80_000)]})
        with execution_config_ctx(device_mode="auto", device_min_rows=1,
                                  mesh_devices=1):
            q = df.groupby("k").agg(col("v").sum().alias("s"))
            text = q.explain_placement()
    finally:
        costmodel.reset_calibration()
    assert "grouped agg" in text and "-> host" in text
    assert "margin:" in text and "rtt" in text and "factorize" in text
    diff = registry().diff(before)
    assert diff.get("placement_decisions_total", 0) >= 1
    assert diff.get("placement_host_wins", 0) >= 1


def test_forced_priced_run_feeds_back_observed(monkeypatch):
    """device_mode=on + DAFT_TPU_PLACEMENT_PRICE_FORCED: the forced dispatch
    carries a priced breakdown AND an observation (total seconds, per-term
    span seconds, dispatches, rows), the error-ratio gauge moves, and
    QueryEnd.placements ships the record."""
    monkeypatch.setenv("DAFT_TPU_PLACEMENT_PRICE_FORCED", "1")
    from daft_tpu.observability.subscribers import (attach_subscriber,
                                                    detach_subscriber)

    ends = []

    class _Sub:
        def on_query_end(self, e):
            ends.append(e)

    before = registry().snapshot()
    sub = _Sub()
    attach_subscriber(sub)
    try:
        df = daft_tpu.from_pydict({
            "k": [i % 13 for i in range(50_000)],
            "v": [float(i % 97) for i in range(50_000)]})
        with execution_config_ctx(device_mode="on", device_min_rows=1,
                                  mesh_devices=1):
            out = (df.groupby("k").agg(col("v").sum().alias("s"))
                   .sort("k").to_pydict())
        assert len(out["k"]) == 13
    finally:
        detach_subscriber(sub)
    diff = registry().diff(before)
    assert diff.get("placement_forced_runs", 0) >= 1
    assert diff.get("placement_feedback_total", 0) >= 1
    assert "cost_model_error_ratio" in diff
    placements = [p for e in ends for p in e.placements]
    assert placements, "QueryEnd carried no placement records"
    rec = next(p for p in placements if p.get("observed"))
    assert rec["forced"] and rec["chosen"] == "device"
    assert rec["device"]["total"] > 0          # priced under PRICE_FORCED
    assert rec["observed"]["total"] > 0
    # observed total is the DEVICE span sum, not the feed-loop wall clock
    # (which includes draining upstream host work) — wall rides along
    assert rec["observed"]["wall"] >= rec["observed"]["total"]
    assert rec["observed"].get("dispatches", 0) >= 1
    assert rec["observed"].get("rows", 0) == 50_000
    assert "error_ratio" in rec


def test_feedback_tee_does_not_steal_profiler_spans(monkeypatch):
    """A query profiled (SpanRecorder active) while placement feedback tees
    device spans must still receive every span — the tee forwards."""
    from daft_tpu.observability.runtime_stats import (SpanRecorder,
                                                      current_spans,
                                                      set_spans)

    outer = SpanRecorder()
    prev = current_spans()
    set_spans(outer)
    try:
        df = daft_tpu.from_pydict({
            "k": [i % 5 for i in range(20_000)],
            "v": [float(i) for i in range(20_000)]})
        with execution_config_ctx(device_mode="on", device_min_rows=1,
                                  mesh_devices=1):
            df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
    finally:
        set_spans(prev)
    names = {s["name"] for s in outer.drain()}
    assert any(n.startswith("device.") for n in names), \
        f"profiler lost device spans to the placement tee: {names}"


# ---------------------------------------------------------------------------
# Surfaces: SQL EXPLAIN PLACEMENT, /api/placement, event-log v9
# ---------------------------------------------------------------------------

def test_sql_explain_placement():
    df = daft_tpu.from_pydict({"a": [1, 2, 3], "b": [1.0, 2.0, 3.0]})
    out = daft_tpu.sql("EXPLAIN PLACEMENT SELECT a, sum(b) AS s FROM df "
                       "GROUP BY a", df=df).to_pydict()
    assert out["explain"][0] == "== Placement Decisions =="
    with pytest.raises(ValueError, match="requires a query"):
        daft_tpu.sql("EXPLAIN PLACEMENT")


def test_api_placement_endpoint():
    from daft_tpu.observability.dashboard import launch
    from urllib.request import urlopen

    d = launch()
    try:
        placement.ledger().record("agg", "host", 42,
                                  device=costmodel.device_ungrouped_cost(
                                      _cal(), 42, 0, 1),
                                  host=costmodel.host_agg_cost(
                                      _cal(), 42, 1, False, False))
        body = json.loads(urlopen(d.url + "/api/placement").read())
        assert {"records", "stats", "error", "calibration"} <= set(body)
        assert body["stats"]["records"] >= 1
        assert any(r["site"] == "agg" for r in body["records"])
        # the placement counters are scrapeable from the first scrape
        text = urlopen(d.url + "/metrics").read().decode()
        assert "daft_tpu_placement_decisions_total" in text
        assert "daft_tpu_cost_model_error_ratio" in text
        assert "daft_tpu_cost_rtt_s" in text
    finally:
        d.shutdown()


def test_event_log_query_end_carries_placements(tmp_path, monkeypatch):
    from daft_tpu.observability.event_log import (disable_event_log,
                                                  enable_event_log)

    monkeypatch.setenv("DAFT_TPU_PLACEMENT_PRICE_FORCED", "1")
    p = str(tmp_path / "ev.jsonl")
    sub = enable_event_log(p)
    try:
        df = daft_tpu.from_pydict({
            "k": [i % 3 for i in range(20_000)],
            "v": [float(i) for i in range(20_000)]})
        with execution_config_ctx(device_mode="on", device_min_rows=1,
                                  mesh_devices=1):
            df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
    finally:
        disable_event_log(sub)
    events = [json.loads(line) for line in open(p)]
    ends = [e for e in events if e["event"] == "query_end"]
    assert ends and all(e["schema_version"] == 11 for e in events)
    placements = [p for e in ends for p in e.get("placements", [])]
    assert placements and placements[0]["site"] in ("agg", "grouped agg")


# ---------------------------------------------------------------------------
# Calibrate tool
# ---------------------------------------------------------------------------

def test_calibrate_suggest_from_records():
    from daft_tpu.tools import calibrate as cal_tool

    calibration = {f.name: getattr(_cal(0.001), f.name)
                   for f in _cal(0.001).__dataclass_fields__.values()}
    # a device-chosen record whose observed h2d ran 4x slower than priced
    # and whose dispatch window (minus the 2-dispatch rtt floor) ran 10x the
    # predicted compute term
    records = [{
        "site": "agg", "chosen": "device", "rows": 100_000,
        "device": {"total": 0.011, "rtt": 0.001, "h2d": 0.004,
                   "compute": 0.006},
        "host": {"total": 0.02, "compute": 0.02},
        "observed": {"total": 0.078, "h2d": 0.016, "dispatch": 0.062,
                     "d2h": 0.0, "rows": 100_000, "dispatches": 2},
        "error_ratio": 7.4,
    }]
    report = cal_tool.suggest(records, calibration)
    assert report["samples"] == 1
    assert report["terms"]["h2d"]["observed_over_predicted"] == 4.0
    # h2d bandwidth scales down by the observed ratio: 1e9 / 4
    assert float(report["suggestions"]["DAFT_TPU_COST_H2D"]) == \
        pytest.approx(2.5e8)
    assert "DAFT_TPU_COST_MM_RATE" in report["suggestions"]
    assert report["error_ratio_median"] == 7.4
    text = cal_tool.render(report)
    assert "suggested overrides" in text and "DAFT_TPU_COST_H2D" in text


def test_calibrate_cli_ledger_mode(tmp_path, capsys):
    from daft_tpu.tools import calibrate as cal_tool

    dump = {"records": [], "calibration": {}}
    p = tmp_path / "ledger.json"
    p.write_text(json.dumps(dump))
    assert cal_tool.main(["--ledger", str(p), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["samples"] == 0 and report["suggestions"] == {}
