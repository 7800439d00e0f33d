"""doctor: ranked triage over flight-recorder anomaly dumps.

``python -m daft_tpu.tools.doctor DUMP.json ...`` reads flight-recorder
anomaly dumps (observability/flight.py) and emits a ranked triage report:
errors and worker deaths first, then stall attribution (scan backpressure),
ledger pressure and admission waits, placement flips, h2d traffic, and a
straggler/skew summary over the ring's query records.

Exit code is always 0 — doctor is a triage lens, not a gate. Stdlib-only on
purpose: it must read a dump without importing the engine.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Sequence


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


def _ring_events(dump: dict, kind: str) -> List[dict]:
    return [ev for ev in dump.get("ring", []) if ev.get("kind") == kind]


def triage_dump(dump: dict, path: str = "") -> List[str]:
    """Ranked triage over one flight-recorder anomaly dump: highest-severity
    findings (errors, deaths) first, then stalls, ledger, placement, h2d,
    straggler/skew."""
    lines = [f"doctor: flight dump {path or '(stdin)'}",
             f"anomaly: {dump.get('kind', '?')} — {dump.get('detail', '')}"]
    if dump.get("tenant"):
        lines.append(f"tenant: {dump['tenant']}")
    metrics = dump.get("metrics", {}) or {}
    queries = _ring_events(dump, "query")
    findings: List[tuple] = []  # (severity, line) — rendered ranked

    errors = [q for q in queries if q.get("error")]
    if errors:
        last = errors[-1]
        findings.append((100, f"{len(errors)} errored quer"
                         f"{'ies' if len(errors) != 1 else 'y'} in the ring; "
                         f"last: {last.get('query_id', '?')}: {last['error']}"))
    # gateway-tier findings: anomaly dumps fired by the serving gateway
    # (daft_tpu/gateway) carry their cause in the dump header and/or the
    # gateway counters in `metrics` — triage-able with no server access
    if dump.get("kind") == "gateway_error":
        findings.append((98, f"gateway error: {dump.get('detail', '?')} — "
                         f"auth_failures="
                         f"{int(metrics.get('gateway_auth_failures', 0))}, "
                         f"wire errors="
                         f"{int(metrics.get('gateway_errors_total', 0))} over "
                         f"{int(metrics.get('gateway_connections_total', 0))} "
                         f"connection(s)"))
    if dump.get("kind") == "cache_thrash":
        findings.append((85, f"result-cache thrash: {dump.get('detail', '?')}"))
    rc_hits = metrics.get("result_cache_hits", 0)
    rc_miss = metrics.get("result_cache_misses", 0)
    if rc_hits or rc_miss:
        rate = rc_hits / max(rc_hits + rc_miss, 1)
        sev = 58 if (rate < 0.5 and dump.get("kind") != "cache_thrash") else 15
        findings.append((sev, f"result cache: {int(rc_hits)} hit(s) / "
                         f"{int(rc_miss)} miss(es) ({rate:.0%} hit rate), "
                         f"{int(metrics.get('result_cache_evictions', 0))} "
                         f"eviction(s), "
                         f"{_fmt_bytes(metrics.get('result_cache_bytes', 0))} "
                         f"resident"))
    deaths = _ring_events(dump, "worker_death")
    if deaths:
        who = ", ".join(f"{d.get('worker_id', '?')} ({d.get('detail', '')})"
                        for d in deaths[-3:])
        findings.append((95, f"{len(deaths)} worker death(s): {who}"))
    fallbacks = _ring_events(dump, "device_fallback")
    if fallbacks:
        findings.append((80, f"{len(fallbacks)} device fallback(s); last: "
                         f"{fallbacks[-1].get('detail', '')}"))
    stall_ms = metrics.get("scan_stall_ms", 0)
    if stall_ms:
        findings.append((70, f"scan backpressure: {int(stall_ms)} ms stalled "
                         f"across {int(metrics.get('scan_backpressure_stalls', 0))} "
                         f"stall(s) — producers paced at the memory wall"))
    pressure = _ring_events(dump, "ledger_pressure")
    if pressure:
        last = pressure[-1]
        findings.append((65, f"{len(pressure)} host-ledger pressure "
                         f"crossing(s); last at "
                         f"{_fmt_bytes(last.get('tracked_bytes', 0))} of "
                         f"{_fmt_bytes(last.get('limit_bytes', 0))}"))
    over = metrics.get("host_over_budget_events", 0)
    if over:
        findings.append((60, f"{int(over)} operator(s) crossed the host "
                         f"budget into spill "
                         f"(spill_bytes {_fmt_bytes(metrics.get('spill_bytes', 0))})"))
    admissions = _ring_events(dump, "admission")
    if admissions:
        total_wait = sum(a.get("wait_s", 0.0) for a in admissions)
        findings.append((55, f"{len(admissions)} HBM admission wait(s), "
                         f"{total_wait:.3f}s total queued"))
    flips = sum(1 for q in queries
                for p in q.get("placements", []) or []
                if isinstance(p, dict) and p.get("tier") in ("host", "cpu"))
    if flips:
        findings.append((50, f"{flips} placement verdict(s) kept stages on "
                         f"host across recent queries"))
    h2d = metrics.get("hbm_h2d_bytes", 0)
    if h2d:
        findings.append((40, f"h2d traffic: {_fmt_bytes(h2d)} uploaded "
                         f"(hbm hits {int(metrics.get('hbm_cache_hits', 0))} / "
                         f"misses {int(metrics.get('hbm_cache_misses', 0))})"))
    # straggler/skew: per-fingerprint wall-clock spread over the ring
    by_fp: Dict[str, List[float]] = {}
    for q in queries:
        if q.get("fingerprint") and not q.get("error"):
            by_fp.setdefault(q["fingerprint"], []).append(q.get("seconds", 0.0))
    for fp, secs in by_fp.items():
        if len(secs) >= 3:
            med = sorted(secs)[len(secs) // 2]
            if med > 0 and max(secs) > 3 * med:
                findings.append((45, f"straggler/skew: plan {fp} spread "
                                 f"{min(secs):.3f}s..{max(secs):.3f}s "
                                 f"(median {med:.3f}s) over {len(secs)} runs"))
    if not findings:
        findings.append((0, "no ranked findings — ring holds "
                         f"{len(dump.get('ring', []))} event(s), "
                         f"{int(dump.get('ring_dropped', 0))} dropped at the cap"))
    findings.sort(key=lambda t: t[0], reverse=True)
    lines.append("findings (ranked):")
    lines.extend(f"  {i + 1}. {msg}" for i, (_, msg) in enumerate(findings))
    if queries:
        lines.append("recent queries:")
        for q in queries[-5:]:
            err = f"  ERROR {q['error']}" if q.get("error") else ""
            lines.append(f"  {q.get('query_id') or '(anon)'}"
                         f"  {q.get('seconds', 0.0):.3f}s"
                         f"  rows={q.get('rows', 0)}{err}")
    return lines


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m daft_tpu.tools.doctor DUMP.json [DUMP.json ...]",
              file=sys.stderr)
        return 0 if argv else 2
    for i, path in enumerate(argv):
        if i:
            print()
        with open(path) as f:
            dump = json.load(f)
        print("\n".join(triage_dump(dump, path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
