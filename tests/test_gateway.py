"""Gateway wire layer: auth, framing, reconnect-resume, concurrent tenants,
result caching/invalidation/eviction, QoS caps on the wire, and the
restartable driver (kill -9 the gateway mid-replay, relaunch, resume).

Everything runs on the CPU backend against loopback sockets. The kill -9
test launches ``python -m daft_tpu.gateway`` as a real subprocess (the only
honest way to test SIGKILL) and is guarded by requires_fault_injection.
"""

import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

import daft_tpu
from daft_tpu.gateway import (CachedResult, GatewayClient, GatewayError,
                              GatewayServer, ResultCache)
from daft_tpu.gateway import protocol as proto
from daft_tpu.observability.metrics import registry
from daft_tpu.serving import FairAdmissionQueue, TenantQueueFull

from fault_injection import requires_fault_injection

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GROUPBY_SQL = "SELECT k, SUM(v) AS s FROM t GROUP BY k ORDER BY k"


def _table(n=20_000, keys=13, salt=0):
    return daft_tpu.from_pydict({
        "k": [i % keys for i in range(n)],
        "v": [float((i + salt) % 1009) for i in range(n)],
        "w": [i % 83 for i in range(n)],
    })


def _ref(df, sql=GROUPBY_SQL):
    return daft_tpu.sql(sql, t=df).to_pydict()


# ---------------------------------------------------------------------------
# auth + framing
# ---------------------------------------------------------------------------

def test_bad_token_rejected_with_typed_error():
    with GatewayServer(tables={"t": _table()},
                       tokens={"acme": "s3cret"}) as srv:
        before = registry().get("gateway_auth_failures")
        with pytest.raises(GatewayError) as ei:
            GatewayClient(srv.host, srv.port, tenant="acme", token="wrong")
        assert ei.value.code == "bad_token"
        # unknown tenant is the same typed rejection (no tenant oracle)
        with pytest.raises(GatewayError) as ei:
            GatewayClient(srv.host, srv.port, tenant="nobody", token="s3cret")
        assert ei.value.code == "bad_token"
        assert registry().get("gateway_auth_failures") >= before + 2
        # the right token still works after the failures
        with GatewayClient(srv.host, srv.port, tenant="acme",
                           token="s3cret") as c:
            assert c.query("SELECT COUNT(*) AS n FROM t")["n"] == [20_000]


def test_open_mode_accepts_any_tenant():
    with GatewayServer(tables={"t": _table()}) as srv:
        with GatewayClient(srv.host, srv.port, tenant="anyone") as c:
            assert c.query("SELECT COUNT(*) AS n FROM t")["n"] == [20_000]


def test_truncated_frame_gets_clean_error_and_server_survives():
    df = _table()
    with GatewayServer(tables={"t": df}) as srv:
        # claim 100 payload bytes, deliver 9, hang up mid-frame
        s = socket.create_connection((srv.host, srv.port), timeout=5)
        s.sendall(struct.pack(">I", 100) + b"J" + b"x" * 9)
        s.close()
        # oversized length prefix: answered with a TYPED error before any
        # payload allocation, then the connection drops
        s = socket.create_connection((srv.host, srv.port), timeout=5)
        proto.send_json(s, {"verb": "hello", "tenant": "a", "token": ""})
        assert proto.recv_json(s)["ok"]
        s.sendall(struct.pack(">I", 1 << 31) + b"J")
        reply = proto.recv_json(s)
        assert reply["ok"] is False and reply["code"] == "frame_too_large"
        s.close()
        # the accept loop and other connections are unharmed
        with GatewayClient(srv.host, srv.port, tenant="a") as c:
            assert c.query(GROUPBY_SQL) == _ref(df)


def test_hello_must_come_first():
    with GatewayServer(tables={"t": _table()}) as srv:
        s = socket.create_connection((srv.host, srv.port), timeout=5)
        proto.send_json(s, {"verb": "execute", "sql": GROUPBY_SQL})
        reply = proto.recv_json(s)
        assert reply["ok"] is False and reply["code"] == "bad_request"
        s.close()


# ---------------------------------------------------------------------------
# prepared handles across reconnects
# ---------------------------------------------------------------------------

def test_reconnect_resumes_prepared_handle():
    df = _table()
    with GatewayServer(tables={"t": df}) as srv:
        c = GatewayClient(srv.host, srv.port, tenant="acme")
        handle = c.prepare(GROUPBY_SQL)
        out1 = c.fetch_pydict(c.execute(handle=handle))
        c.close()
        # a brand-new connection executes by the SAME handle — handles are
        # server-scoped, not connection-scoped
        with GatewayClient(srv.host, srv.port, tenant="acme") as c2:
            out2 = c2.fetch_pydict(c2.execute(handle=handle))
        assert out1 == out2 == _ref(df)


def test_unknown_handle_is_typed_and_client_reprepares():
    df = _table()
    with GatewayServer(tables={"t": df}) as srv:
        with GatewayClient(srv.host, srv.port, tenant="acme") as c:
            with pytest.raises(GatewayError) as ei:
                c.execute(handle="feedfacedeadbeef01234567")
            assert ei.value.code == "unknown_handle"
            # a handle the CLIENT prepared transparently re-prepares from the
            # remembered SQL even after the server forgets it
            handle = c.prepare(GROUPBY_SQL)
            srv._handles.clear()  # simulate eviction/restart
            assert c.fetch_pydict(c.execute(handle=handle)) == _ref(df)


# ---------------------------------------------------------------------------
# concurrent tenants: wire results bit-identical to in-process execution
# ---------------------------------------------------------------------------

def test_concurrent_tenants_bit_identical_to_in_process():
    df = _table(30_000)
    sqls = {
        "groupby": GROUPBY_SQL,
        "filter": "SELECT SUM(v) AS s FROM t WHERE w > 40",
        "minmax": "SELECT w, MIN(v) AS lo, MAX(v) AS hi FROM t "
                  "GROUP BY w ORDER BY w",
    }
    ref = {name: _ref(df, s) for name, s in sqls.items()}
    failures = []
    with GatewayServer(tables={"t": df}, max_concurrent=2) as srv:

        def tenant_thread(tid):
            try:
                with GatewayClient(srv.host, srv.port,
                                   tenant=f"tenant-{tid}") as c:
                    names = list(sqls)
                    for i in range(6):
                        name = names[(tid + i) % len(names)]
                        out = c.query(sqls[name])
                        if out != ref[name]:
                            failures.append((tid, name))
            except Exception as e:  # noqa: BLE001 — surfaced via the list
                failures.append((tid, repr(e)))

        threads = [threading.Thread(target=tenant_thread, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert not failures, failures


# ---------------------------------------------------------------------------
# result cache: hits, source-change invalidation, eviction, thrash
# ---------------------------------------------------------------------------

def test_result_cache_hit_on_repeat_and_invalidation_on_source_change():
    df = _table(salt=0)
    with GatewayServer(tables={"t": df}) as srv:
        with GatewayClient(srv.host, srv.port, tenant="a") as c:
            out1 = c.query(GROUPBY_SQL)
            assert c.last_source == "executed"
            out2 = c.query(GROUPBY_SQL)
            assert c.last_source == "result_cache"
            assert out1 == out2
            # rebind the table to DIFFERENT data: content fingerprints
            # change, the old cache key is unreachable, the query
            # re-executes and returns the NEW data's answer
            df2 = _table(salt=7)
            srv.set_table("t", df2)
            out3 = c.query(GROUPBY_SQL)
            assert c.last_source == "executed"
            assert out3 == _ref(df2) and out3 != out1
            # and the new result caches independently
            assert c.query(GROUPBY_SQL) == out3
            assert c.last_source == "result_cache"


def test_result_cache_bounded_eviction_under_tiny_budget():
    cache = ResultCache(budget_bytes=1000)
    def entry(size):
        return CachedResult([b"x" * size], rows=1, columns=["a"])
    before = registry().get("result_cache_evictions")
    cache.put("k1", entry(400))
    cache.put("k2", entry(400))
    assert cache.stats()["entries"] == 2
    cache.put("k3", entry(400))  # over budget: k1 (LRU) evicted
    st = cache.stats()
    assert st["entries"] == 2 and st["bytes"] <= 1000
    assert cache.get("k1") is None
    assert cache.get("k3") is not None
    assert registry().get("result_cache_evictions") > before
    # an entry larger than the whole budget is refused, not thrashed in
    assert cache.put("huge", entry(2000)) is False
    assert cache.get("k3") is not None


def test_result_cache_zero_budget_disables():
    cache = ResultCache(budget_bytes=0)
    assert cache.put("k", CachedResult([b"x"], 1, ["a"])) is False
    assert cache.get("k") is None


def test_result_cache_thrash_detection(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_GATEWAY_THRASH_WINDOW", "8")
    cache = ResultCache(budget_bytes=100)
    # repeat traffic (2 distinct keys) that never hits: thrash
    for _ in range(4):
        cache.get("a")
        cache.get("b")
    detail = cache.note_thrash()
    assert detail is not None and "thrash" in detail
    # window consumed: one sustained burst -> one trigger
    assert cache.note_thrash() is None


# ---------------------------------------------------------------------------
# QoS: queue caps surface as typed wire errors
# ---------------------------------------------------------------------------

def test_tenant_queue_cap_raises_tenant_queue_full(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TENANT_QUEUE_CAP_CAPPED", "2")
    q = FairAdmissionQueue()
    q.push("capped", "x0")
    q.push("capped", "x1")
    with pytest.raises(TenantQueueFull):
        q.push("capped", "x2")
    # other tenants are unaffected
    for i in range(5):
        q.push("free", f"y{i}")


def test_tenant_weights_order(monkeypatch):
    monkeypatch.setenv("DAFT_TPU_TENANT_WEIGHT_HEAVY", "3")
    q = FairAdmissionQueue()
    for i in range(6):
        q.push("heavy", f"h{i}")
    for i in range(3):
        q.push("light", f"l{i}")
    order = [q.pop(0) for _ in range(9)]
    # weight-3 tenant drains 3 per rotation visit, weight-1 gets 1
    assert order == ["h0", "h1", "h2", "l0", "h3", "h4", "h5", "l1", "l2"]


def test_over_capacity_maps_to_typed_wire_error():
    df = _table()
    with GatewayServer(tables={"t": df}) as srv:
        def full(*a, **k):
            raise TenantQueueFull("a", 1, 1)
        srv._session.submit = full
        # bypass the result cache (fresh query text) so execute reaches submit
        with GatewayClient(srv.host, srv.port, tenant="a") as c:
            with pytest.raises(GatewayError) as ei:
                c.execute(sql="SELECT SUM(w) AS sw FROM t")
            assert ei.value.code == "over_capacity"


# ---------------------------------------------------------------------------
# cancellation over the wire
# ---------------------------------------------------------------------------

def test_cancel_queued_query_yields_typed_cancelled_error():
    df = _table()
    with GatewayServer(tables={"t": df}, max_concurrent=1) as srv:
        with GatewayClient(srv.host, srv.port, tenant="a") as c:
            qid = c.execute(sql=GROUPBY_SQL)
            assert c.cancel(qid) in (True, False)
            # whichever side won the race, fetch answers deterministically:
            # a typed cancelled error or the full (correct) result
            try:
                out = c.fetch_pydict(qid)
                assert out == _ref(df)
            except GatewayError as e:
                assert e.code == "cancelled"


# ---------------------------------------------------------------------------
# observability: /api/gateway rollup + gateway query records
# ---------------------------------------------------------------------------

def test_gateway_query_records_and_dashboard_rollup():
    import json as _json
    import urllib.request

    from daft_tpu.observability.dashboard import launch

    df = _table()
    dash = launch()
    try:
        with GatewayServer(tables={"t": df}) as srv:
            with GatewayClient(srv.host, srv.port, tenant="acme") as c:
                c.query(GROUPBY_SQL)
                c.query(GROUPBY_SQL)
        with urllib.request.urlopen(dash.url + "/api/gateway",
                                    timeout=10) as r:
            body = _json.load(r)
        acme = body["tenants"]["acme"]
        assert acme["queries"] == 2
        assert acme["executed"] == 1 and acme["result_cache"] == 1
        assert acme["cache_hit_rate"] == 0.5
        assert acme["bytes_streamed"] > 0
        assert body["counters"].get("result_cache_hits", 0) >= 1
    finally:
        dash.shutdown()


def test_gateway_error_and_thrash_are_flight_anomalies(monkeypatch, tmp_path):
    from daft_tpu.observability import flight

    monkeypatch.setenv("DAFT_TPU_FLIGHT_RECORDER", "1")
    monkeypatch.setenv("DAFT_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("DAFT_TPU_ANOMALY_COOLDOWN_S", "0")
    flight._reset_for_tests()
    try:
        with GatewayServer(tables={"t": _table()},
                           tokens={"acme": "good"}) as srv:
            with pytest.raises(GatewayError):
                GatewayClient(srv.host, srv.port, tenant="acme", token="bad")
            frec = flight.recorder()
            assert frec is not None
            assert frec.dumps, "auth failure produced no dump"
        # thrash trigger path: tiny budget + repeat misses through execute
        with GatewayServer(tables={"t": _table(5000)},
                           result_cache_budget=64) as srv:
            with GatewayClient(srv.host, srv.port, tenant="a") as c:
                # results never fit in 64 bytes -> every repeat misses; the
                # sliding window fills and fires cache_thrash
                for _ in range(40):
                    c.query(GROUPBY_SQL)
        dumps_text = " ".join(frec.dumps)
        assert "cache_thrash" in dumps_text, frec.dumps
    finally:
        flight._reset_for_tests()


def test_doctor_triages_gateway_dump(tmp_path):
    import json as _json

    from daft_tpu.tools.doctor import triage_dump

    dump = {
        "kind": "cache_thrash",
        "detail": "result-cache thrash: hit rate 0.10 over last 32 lookups",
        "ring": [],
        "metrics": {"result_cache_hits": 3, "result_cache_misses": 29,
                    "result_cache_evictions": 14, "result_cache_bytes": 512,
                    "gateway_connections_total": 5},
    }
    lines = "\n".join(triage_dump(dump, "dump.json"))
    assert "result-cache thrash" in lines
    assert "hit rate" in lines
    gw = {
        "kind": "gateway_error",
        "detail": "auth failure for tenant 'acme'",
        "ring": [],
        "metrics": {"gateway_auth_failures": 3,
                    "gateway_connections_total": 7},
    }
    lines = "\n".join(triage_dump(gw, "gw.json"))
    assert "gateway error" in lines and "auth_failures=3" in lines


# ---------------------------------------------------------------------------
# restartable driver: kill -9 the gateway, relaunch, resume from checkpoints
# ---------------------------------------------------------------------------

def _spawn_gateway(ckpt_dir, rows=8000):
    """Launch python -m daft_tpu.gateway as a real subprocess and parse the
    bound port from its banner. The child is pinned to the CPU platform and
    the host path: a chip belongs to one process, and this one is not it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["DAFT_TPU_DEVICE"] = "off"
    env["DAFT_TPU_CHECKPOINT_DIR"] = str(ckpt_dir)
    proc = subprocess.Popen(
        [sys.executable, "-m", "daft_tpu.gateway", "--port", "0",
         "--demo-rows", str(rows)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env)
    banner = []

    def read():
        banner.append(proc.stdout.readline())

    t = threading.Thread(target=read, daemon=True)
    t.start()
    t.join(timeout=120)
    assert banner and banner[0], \
        f"gateway printed no banner (rc={proc.poll()})"
    assert "gateway listening on" in banner[0], banner[0]
    host, port = banner[0].rsplit(" ", 1)[1].strip().rsplit(":", 1)
    return proc, host, int(port)


@requires_fault_injection
def test_kill9_gateway_mid_replay_relaunch_resumes(tmp_path):
    """The restartable-driver acceptance: SIGKILL the gateway process while
    a replay stream is in flight, relaunch against the same checkpoint root,
    and the relaunched gateway serves every committed query from checkpoint
    (bit-identical) and re-runs the rest — no client-visible wrong result."""
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    sqls = [
        "SELECT k, SUM(v) AS s FROM t GROUP BY k ORDER BY k",
        "SELECT SUM(v) AS s FROM t WHERE w > 48",
        "SELECT w, MIN(v) AS lo FROM t GROUP BY w ORDER BY w",
    ]
    proc, host, port = _spawn_gateway(ckpt)
    try:
        c = GatewayClient(host, port, tenant="replay", timeout=120)
        first = {}
        # two queries complete (and COMMIT checkpoints); the third is
        # submitted and the gateway dies before its fetch completes
        first[0] = c.query(sqls[0])
        first[1] = c.query(sqls[1])
        assert c.last_source == "executed"
        c.execute(sql=sqls[2])  # in flight, never fetched
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        with pytest.raises((GatewayError, OSError, EOFError)):
            c.query(sqls[0])
        c.close()
    finally:
        if proc.poll() is None:
            proc.kill()
    # relaunch over the same checkpoint root: same demo table (deterministic
    # construction -> same content fingerprints -> same checkpoint keys)
    proc2, host2, port2 = _spawn_gateway(ckpt)
    try:
        with GatewayClient(host2, port2, tenant="replay", timeout=120) as c2:
            # committed queries come back from CHECKPOINT, bit-identical
            out0 = c2.fetch_pydict(c2.execute(sql=sqls[0]))
            assert c2.last_source == "checkpoint", c2.last_source
            assert out0 == first[0]
            out1 = c2.fetch_pydict(c2.execute(sql=sqls[1]))
            assert c2.last_source == "checkpoint"
            assert out1 == first[1]
            # the in-flight (uncommitted) query simply re-runs — correct
            # result, no stale serve
            out2 = c2.fetch_pydict(c2.execute(sql=sqls[2]))
            assert c2.last_source in ("executed", "checkpoint")
            assert len(out2["w"]) > 0
    finally:
        proc2.kill()
        proc2.wait(timeout=30)


def test_checkpoint_restore_across_server_instances(tmp_path, monkeypatch):
    """In-process flavor of the restartable driver (no subprocess): a second
    GatewayServer over the same checkpoint root serves the first server's
    committed result from disk."""
    monkeypatch.setenv("DAFT_TPU_CHECKPOINT_DIR", str(tmp_path))
    df = _table()
    with GatewayServer(tables={"t": df}) as srv:
        with GatewayClient(srv.host, srv.port, tenant="a") as c:
            out1 = c.query(GROUPBY_SQL)
            assert c.last_source == "executed"
    with GatewayServer(tables={"t": df}) as srv2:
        with GatewayClient(srv2.host, srv2.port, tenant="a") as c:
            out2 = c.query(GROUPBY_SQL)
            assert c.last_source == "checkpoint"
            assert out2 == out1
