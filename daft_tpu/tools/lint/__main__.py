"""CLI: python -m daft_tpu.tools.lint [paths...] [--json] [--write-baseline]
[--repin-schema] [--no-baseline] [--baseline PATH]

Exit status 0 = clean (baseline respected), 1 = actionable findings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from . import policy
from .engine import (build_project, run_rules, apply_suppressions,
                     apply_baseline, load_baseline, LintResult)
from .obs_rules import event_schema_fingerprint, read_schema_version

_HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BASELINE = os.path.join(_HERE, "baseline.json")
SCHEMA_PIN = os.path.join(_HERE, "schema_pin.json")


def _repo_root() -> str:
    # daft_tpu/tools/lint/__main__.py -> repo root is three levels above daft_tpu
    return os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))


def _repin_schema(root: str) -> int:
    project = build_project(root, [os.path.join(root, "daft_tpu")])
    events = project.by_rel.get(policy.EVENTS_MODULE)
    event_log = project.by_rel.get(policy.EVENT_LOG_MODULE)
    if events is None or event_log is None:
        print("cannot repin: events/event_log modules not found", file=sys.stderr)
        return 2
    pin = {"schema_version": read_schema_version(event_log),
           "fingerprint": event_schema_fingerprint(events)}
    with open(SCHEMA_PIN, "w", encoding="utf-8") as fh:
        json.dump(pin, fh, indent=2)
        fh.write("\n")
    print(f"pinned event schema v{pin['schema_version']} "
          f"fingerprint {pin['fingerprint'][:12]}…")
    return 0


def _write_baseline(path: str, result_findings) -> None:
    old = load_baseline(path)
    grouped = {}
    for f in result_findings:
        grouped.setdefault((f.file, f.rule), 0)
        grouped[(f.file, f.rule)] += 1
    entries = []
    for (file, rule), count in sorted(grouped.items()):
        prev = old.get((file, rule), {})
        entries.append({"file": file, "rule": rule, "count": count,
                        "why": prev.get("why", "TODO: justify or fix")})
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"entries": entries}, fh, indent=2)
        fh.write("\n")
    print(f"baseline written: {len(entries)} (file, rule) entries "
          f"covering {sum(grouped.values())} findings")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m daft_tpu.tools.lint")
    ap.add_argument("paths", nargs="*", help="files/dirs (default: daft_tpu/)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings + per-rule counts")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--no-baseline", action="store_true")
    ap.add_argument("--write-baseline", action="store_true",
                    help="grandfather the current findings")
    ap.add_argument("--repin-schema", action="store_true",
                    help="re-pin the event-record field-set fingerprint "
                    "against the current SCHEMA_VERSION")
    args = ap.parse_args(argv)

    root = _repo_root()
    if args.repin_schema:
        return _repin_schema(root)

    paths = [os.path.abspath(p) for p in args.paths] or \
        [os.path.join(root, "daft_tpu")]
    project = build_project(root, paths)
    raw = run_rules(project)
    kept, n_sup = apply_suppressions(project, raw)

    if args.write_baseline:
        _write_baseline(args.baseline, kept)
        return 0

    result = LintResult(suppressed=n_sup)
    baseline = {} if args.no_baseline else load_baseline(args.baseline)
    result.findings = apply_baseline(kept, baseline, result)

    if args.json:
        print(json.dumps(result.to_json(), indent=2))
    else:
        for f in result.findings:
            print(f.render())
        n_grand = sum(result.grandfathered.values())
        summary = (f"{len(result.findings)} finding(s), "
                   f"{result.suppressed} suppressed, "
                   f"{n_grand} grandfathered by baseline")
        print(("FAIL: " if result.findings else "ok: ") + summary)
    return 1 if result.findings else 0


if __name__ == "__main__":
    sys.exit(main())
