"""daft_tpu console entry point (reference parity: daft/cli.py + daft-cli).

    python -m daft_tpu info                 # engine/backend/device summary
    python -m daft_tpu sql "SELECT ..."     # run SQL over registered files
    python -m daft_tpu schema PATH          # print a file's inferred schema
"""

from __future__ import annotations

import argparse
import json


def _cmd_info(_args) -> int:
    import daft_tpu

    print(f"daft_tpu {daft_tpu.__version__}")
    try:
        from .utils import jax_setup  # noqa: F401
        import jax

        print(f"jax {jax.__version__} backend={jax.default_backend()} "
              f"devices={[str(d) for d in jax.devices()]}")
    except Exception as e:  # pragma: no cover
        print(f"jax unavailable: {e}")
    from .config import execution_config

    print(f"execution config: {execution_config()}")
    return 0


def _cmd_sql(args) -> int:
    import daft_tpu

    session_tables = {}
    for spec in args.table or []:
        name, path = spec.split("=", 1)
        if path.endswith((".parquet", ".pq")) or "*" in path:
            session_tables[name] = daft_tpu.read_parquet(path)
        elif path.endswith(".csv"):
            session_tables[name] = daft_tpu.read_csv(path)
        else:
            session_tables[name] = daft_tpu.read_json(path)
    df = daft_tpu.sql(args.query, **session_tables)
    out = df.limit(args.limit).to_pydict() if args.limit else df.to_pydict()
    if args.json:
        print(json.dumps(out, default=str))
    else:
        cols = list(out)
        n = len(out[cols[0]]) if cols else 0
        print(" | ".join(cols))
        for i in range(n):
            print(" | ".join(str(out[c][i]) for c in cols))
    return 0


def _cmd_schema(args) -> int:
    import daft_tpu

    path = args.path
    if path.endswith((".parquet", ".pq")):
        df = daft_tpu.read_parquet(path)
    elif path.endswith((".csv", ".tsv")):
        df = daft_tpu.read_csv(path)
    else:
        df = daft_tpu.read_json(path)
    for f in df.schema:
        print(f"{f.name}: {f.dtype}")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="daft_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)
    sub.add_parser("info")
    sp = sub.add_parser("sql")
    sp.add_argument("query")
    sp.add_argument("--table", "-t", action="append",
                    help="name=path bindings usable in the query")
    sp.add_argument("--limit", type=int, default=0)
    sp.add_argument("--json", action="store_true")
    sc = sub.add_parser("schema")
    sc.add_argument("path")
    args = p.parse_args(argv)
    return {"info": _cmd_info, "sql": _cmd_sql,
            "schema": _cmd_schema}[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
