"""Flight recorder: bounded ring + drop accounting, zero-overhead off-switch,
per-kind anomaly triggers (slow query EMA, query error, ledger pressure,
device fallback, worker death), multi-tenant dump no-bleed under a threaded
serving hammer, and the doctor CLI over fresh dumps."""

import json
import os
import sys
import threading
import subprocess

import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.observability import flight
from daft_tpu.observability.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Each test resolves its own recorder from (monkeypatched) env; drop the
    cached resolution on both sides so no test inherits another's knobs."""
    flight._reset_for_tests()
    yield
    flight._reset_for_tests()


def _recorder(monkeypatch, tmp_path, ring=8, wall_k=1.0, min_s=0.0,
              cooldown=0.0):
    monkeypatch.setenv("DAFT_TPU_FLIGHT_RECORDER", "1")
    monkeypatch.setenv("DAFT_TPU_FLIGHT_RING", str(ring))
    monkeypatch.setenv("DAFT_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("DAFT_TPU_ANOMALY_WALL_K", str(wall_k))
    monkeypatch.setenv("DAFT_TPU_ANOMALY_MIN_S", str(min_s))
    monkeypatch.setenv("DAFT_TPU_ANOMALY_COOLDOWN_S", str(cooldown))
    rec = flight.recorder()
    assert rec is not None
    return rec


def _dumps(tmp_path):
    return sorted(str(p) for p in tmp_path.glob("flight_*.json"))


# ---------------------------------------------------------------------------
# ring discipline
# ---------------------------------------------------------------------------

def test_ring_bounded_with_drop_accounting_and_registry_silent(monkeypatch,
                                                               tmp_path):
    rec = _recorder(monkeypatch, tmp_path, ring=8)
    before = registry().snapshot()
    for i in range(30):
        rec.record("query", query_id=f"q{i}", seconds=0.001)
    assert len(rec.snapshot()) == 8
    assert rec.dropped == 22
    # newest events survive, oldest evicted FIFO
    assert [ev["query_id"] for ev in rec.snapshot()] == \
        [f"q{i}" for i in range(22, 30)]
    assert rec.snapshot(limit=3) == rec.snapshot()[-3:]
    # ring maintenance (appends AND evictions) never touches the registry —
    # the tier-1 empty-diff guard must hold with the recorder ON
    assert registry().diff(before) == {}
    assert not _dumps(tmp_path)


def test_recorder_off_is_none_and_registry_silent(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_FLIGHT_RECORDER", "0")
    before = registry().snapshot()
    assert flight.recorder() is None
    assert flight.recorder() is None  # resolved once, stays None
    # a full query through the native runner with the recorder off must
    # leave no flight_* trace (the hook sites skip on one `is None` test)
    df = dt.from_pydict({"k": [1, 2, 1, 2], "v": [1.0, 2.0, 3.0, 4.0]})
    df.groupby("k").agg(col("v").sum().alias("s")).sort("k").to_pydict()
    after = registry().snapshot()
    assert {k: v for k, v in registry().diff(before).items()
            if k.startswith("flight_")} == {}
    assert after.get("flight_anomalies_total", 0) == \
        before.get("flight_anomalies_total", 0)


def test_ring_hammer_from_many_threads_stays_bounded(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path, ring=16)
    n_threads, per_thread = 8, 200

    def hammer(tid):
        for i in range(per_thread):
            rec.record("query", tenant=f"t{tid}", query_id=f"{tid}-{i}")

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec.snapshot()) == 16
    assert rec.dropped == n_threads * per_thread - 16


# ---------------------------------------------------------------------------
# anomaly triggers, one per kind
# ---------------------------------------------------------------------------

def test_slow_query_trigger_via_ema(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path, wall_k=2.0)
    a0 = registry().get("flight_anomalies_total")
    rec.note_query("planA", 0.01)           # seeds the EMA, cannot trigger
    rec.note_query("planA", 0.012)          # within 2x: no trigger
    assert not _dumps(tmp_path)
    rec.note_query("planA", 0.5)            # 0.5 > 2x EMA(~0.01): trigger
    dumps = _dumps(tmp_path)
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["kind"] == "slow_query"
    assert "planA" in dump["detail"] and "EMA" in dump["detail"]
    assert dump["ema"]["planA"] > 0
    assert [ev["kind"] for ev in dump["ring"]].count("query") == 3
    assert registry().get("flight_anomalies_total") - a0 == 1
    assert rec.dumps == dumps


def test_slow_query_floor_suppresses_fast_queries(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path, wall_k=1.0, min_s=10.0)
    rec.note_query("planA", 0.001)
    rec.note_query("planA", 1.0)            # 1000x the EMA but under the floor
    assert not _dumps(tmp_path)


def test_query_error_trigger(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path)
    rec.note_query("planB", 0.01, query_id="qerr",
                   error="ValueError: boom")
    dumps = _dumps(tmp_path)
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["kind"] == "query_error"
    assert dump["query_id"] == "qerr"
    assert "boom" in dump["detail"]


def test_ledger_pressure_crossing_triggers(monkeypatch, tmp_path):
    from daft_tpu.config import execution_config_ctx
    from daft_tpu.memory import manager

    _recorder(monkeypatch, tmp_path)
    m = manager()
    m.clear()
    try:
        with execution_config_ctx(memory_limit_bytes=1000,
                                  memory_pressure=0.8):
            m.track(700)                    # below threshold: no anomaly
            assert not _dumps(tmp_path)
            m.track(200)                    # 900 >= 800: upward crossing
            dumps = _dumps(tmp_path)
            assert len(dumps) == 1
            with open(dumps[0]) as f:
                dump = json.load(f)
            assert dump["kind"] == "ledger_pressure"
            ev = [e for e in dump["ring"] if e["kind"] == "ledger_pressure"]
            assert ev and ev[0]["tracked_bytes"] == 900
            assert ev[0]["limit_bytes"] == 1000
            m.track(50)                     # still in pressure: no re-fire
            assert len(_dumps(tmp_path)) == 1
    finally:
        m.clear()


def test_device_fallback_trigger(monkeypatch, tmp_path):
    from daft_tpu.observability import placement

    _recorder(monkeypatch, tmp_path)

    class DeviceFallback(Exception):
        pass

    with pytest.raises(DeviceFallback):
        with placement.feedback(None):
            raise DeviceFallback("device refused the batch")
    dumps = _dumps(tmp_path)
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["kind"] == "device_fallback"
    assert "device refused the batch" in dump["detail"]


def test_worker_death_trigger(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path)
    rec.note_worker_death("worker-3", "no heartbeat for 1.0s")
    dumps = _dumps(tmp_path)
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["kind"] == "worker_death"
    assert "worker-3" in dump["detail"]


def test_cooldown_suppresses_dumps_but_counts_anomalies(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path, cooldown=60.0)
    a0 = registry().get("flight_anomalies_total")
    d0 = registry().get("flight_dumps_total")
    for _ in range(5):
        rec.note_query("p", 0.0, error="boom")
    assert len(_dumps(tmp_path)) == 1       # first dump only, rest cooled down
    assert registry().get("flight_anomalies_total") - a0 == 5
    assert registry().get("flight_dumps_total") - d0 == 1


def test_unwritable_dump_dir_degrades_to_counter(monkeypatch, tmp_path):
    bad = tmp_path / "nope"
    bad.write_text("a file, not a directory")
    monkeypatch.setenv("DAFT_TPU_FLIGHT_DIR", str(bad))
    monkeypatch.setenv("DAFT_TPU_ANOMALY_COOLDOWN_S", "0")
    flight._reset_for_tests()
    rec = flight.recorder()
    f0 = registry().get("flight_dump_failures")
    rec.note_query("p", 0.0, error="boom")  # must not raise
    assert registry().get("flight_dump_failures") - f0 == 1
    assert rec.dumps == []


def test_native_runner_records_queries_in_ring(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path, wall_k=100.0, min_s=100.0)
    df = dt.from_pydict({"k": [1, 2, 1, 2], "v": [1.0, 2.0, 3.0, 4.0]})
    out = df.groupby("k").agg(col("v").sum().alias("s")).sort("k").to_pydict()
    assert out == {"k": [1, 2], "s": [4.0, 6.0]}
    queries = [ev for ev in rec.snapshot() if ev["kind"] == "query"]
    assert queries, "native runner never reached the flight recorder"
    q = queries[-1]
    assert q["fingerprint"] and q["seconds"] > 0 and q["query_id"]
    assert q["rows"] == 2
    assert not _dumps(tmp_path)


def test_subscriber_sees_flight_anomaly(monkeypatch, tmp_path):
    from daft_tpu.observability import attach_subscriber, detach_subscriber
    from daft_tpu.observability.subscribers import Subscriber

    rec = _recorder(monkeypatch, tmp_path)
    seen = []

    class Sub(Subscriber):
        def on_flight_anomaly(self, event):
            seen.append(event)

    sub = Sub()
    attach_subscriber(sub)
    try:
        rec.note_query("p", 0.0, query_id="qx", error="boom")
    finally:
        detach_subscriber(sub)
    assert len(seen) == 1
    assert seen[0].kind == "query_error" and seen[0].query_id == "qx"
    assert seen[0].dump_path and os.path.exists(seen[0].dump_path)


# ---------------------------------------------------------------------------
# multi-tenant no-bleed under a threaded serving hammer
# ---------------------------------------------------------------------------

def test_serving_hammer_dump_has_no_cross_tenant_bleed(monkeypatch, tmp_path):
    """N client threads hammer one ServingSession under distinct tenants; one
    tenant's query errors. The ring stays bounded, and the query_error dump
    carries ONLY the erroring tenant's (and engine-global) events — never
    another tenant's queries."""
    from daft_tpu.serving import ServingSession

    rec = _recorder(monkeypatch, tmp_path, ring=64)
    df = dt.from_pydict({"k": [i % 7 for i in range(500)],
                         "v": [float(i) for i in range(500)]})

    @dt.func
    def boom(x: int) -> int:
        raise ValueError("tenant-bad exploded")

    mk_good = lambda: df.groupby("k").agg(col("v").sum().alias("s")).sort("k")
    errors = []
    with ServingSession(max_concurrent=4) as sess:
        def good_client(tid):
            for _ in range(6):
                sess.submit(mk_good(), tenant=f"t{tid}").to_pydict()

        threads = [threading.Thread(target=good_client, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        try:
            sess.submit(df.select(boom(col("k"))),
                        tenant="bad").result(timeout=60)
        except Exception as e:  # lint: ignore[broad-except] -- the erroring
            # tenant's exception type is the UDF runtime's to choose; the
            # assertion below is on the recorded anomaly, not the type
            errors.append(e)
        for t in threads:
            t.join()
    assert errors, "the bad tenant's query never errored"
    assert len(rec.snapshot()) <= 64
    dumps = [p for p in _dumps(tmp_path) if "query_error" in p]
    assert dumps, "no query_error dump from the serving hammer"
    with open(dumps[-1]) as f:
        dump = json.load(f)
    assert dump["tenant"] == "bad"
    tenants = {ev.get("tenant", "") for ev in dump["ring"]}
    assert tenants <= {"", "bad"}, \
        f"cross-tenant bleed in anomaly dump: {tenants}"
    # the hammer's other tenants DID flow through the recorder (the filter
    # dropped them from the dump; they were not simply absent)
    all_tenants = {ev.get("tenant", "") for ev in rec.snapshot()}
    assert any(t.startswith("t") for t in all_tenants)


# ---------------------------------------------------------------------------
# doctor CLI
# ---------------------------------------------------------------------------

def test_doctor_reads_flight_dump(monkeypatch, tmp_path):
    rec = _recorder(monkeypatch, tmp_path)
    rec.record("admission", tenant="t0", query_id="qa", wait_s=0.25,
               est_pin_bytes=1 << 20)
    rec.note_query("p1", 0.05, query_id="q1", rows=10)
    rec.note_worker_death("worker-1", "connection closed")
    rec.note_query("p1", 0.01, query_id="q2", rows=10,
                   error="RuntimeError: shard lost")
    dumps = _dumps(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "daft_tpu.tools.doctor"] + dumps,
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    # the error dump's triage ranks the error and the worker death first
    assert "shard lost" in out.stdout
    assert "worker death" in out.stdout
    assert "findings (ranked):" in out.stdout
    assert "admission wait" in out.stdout


