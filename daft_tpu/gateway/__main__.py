"""``python -m daft_tpu.gateway`` — run the gateway as a standalone server.

    python -m daft_tpu.gateway --port 8642 --demo-rows 200000

Prints ``gateway listening on HOST:PORT`` once the socket is bound (tests
and scripts parse this line to learn the chosen port when --port 0), then
serves until SIGINT/SIGTERM. ``--demo-rows N`` registers a deterministic
demo table ``t`` (k = i%601, v = float(i%8191),
w = i%97) — deterministic ON PURPOSE: the same rows on every launch means
the same source content fingerprints, which is what lets a relaunched
gateway resume its predecessor's committed checkpoints and hit its persisted
result keys (the restartable-driver demo and the kill -9 test both ride
this). Real deployments register tables in-process via
``GatewayServer.set_table`` instead.
"""

from __future__ import annotations

import argparse
import signal
import threading


def _demo_table(rows: int):
    import daft_tpu as dt

    return dt.from_pydict({
        "k": [i % 601 for i in range(rows)],
        "v": [float(i % 8191) for i in range(rows)],
        "w": [i % 97 for i in range(rows)],
    })


def main(argv=None) -> int:
    from .server import GatewayServer

    p = argparse.ArgumentParser(
        prog="python -m daft_tpu.gateway",
        description="daft_tpu serving gateway (wire protocol over TCP)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="bind port (0 = pick a free port; printed on stdout)")
    p.add_argument("--demo-rows", type=int, default=0, metavar="N",
                   help="register a deterministic N-row demo table 't'")
    p.add_argument("--max-concurrent", type=int, default=None,
                   help="serving worker threads (default: ExecutionConfig)")
    args = p.parse_args(argv)

    tables = {"t": _demo_table(args.demo_rows)} if args.demo_rows > 0 else None
    server = GatewayServer(host=args.host, port=args.port, tables=tables,
                           max_concurrent=args.max_concurrent)
    server.start()
    print(f"gateway listening on {server.host}:{server.port}", flush=True)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    server.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
