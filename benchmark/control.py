"""Read the two numbers a float limit is set from, at a cell's own size.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --program <0|1>

For each seed it makes the cell's tables and compares, against the plain
reference in float64:

- with `--program 1`, the program's answers (each template run twice through
  the timed path's own call, `run.execute`, under the default configuration;
  needs the TPU): the sound runs' gaps, whose largest sets the limit's floor;
- the control: the same reference with every floating-point column rounded to
  bfloat16 as it is read (`reference.to_bfloat16`), the precision below the
  float32 planes the configuration states. Its smallest gap sets the ceiling.

The benchmark's own runs never call this. `tests/benchmark_harness/` keeps the
control as a test at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare as cmp  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--program", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    run.refuse_program_knobs(os.environ)
    run.place_compile_cache(root, os.environ)
    sys.path.insert(0, root)
    cell = run.Cell(root, args.workload)
    if args.program:
        run.find_device(cell.workload["chips"], run.load_json(
            os.path.join(cell.bench_dir, "peaks.json")))
        import daft_tpu as dt

    sound, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        arrow = cell.datagen.generate(cell.config["scale_factor"], seed, cell.tables_read())
        worst_control = 0.0
        tables = ({t: dt.from_arrow(a).collect() for t, a in arrow.items()}
                  if args.program else None)
        for name in cell.templates:
            rec = {"seed": seed, "template": name}
            ref = cell.reference.answer(name, arrow)
            # the control put in the program's place: what the comparison reads
            c = cmp.compare(ref, cell.reference.answer(
                name, arrow, cell.reference.to_bfloat16))
            rec["control"] = c
            worst_control = max(worst_control, c["float_rel_gap"],
                                float("inf") if c["shape"] or c["exact_mismatches"] else 0.0)
            if args.program:
                for _ in (1, 2):
                    got = run.execute(cell.queries.TEMPLATES[name]["program"], tables)
                rec["program"] = cmp.compare(ref, got)
                sound.append(rec["program"])
            print(json.dumps(rec), flush=True)
        del tables
        control.append(worst_control)
    summary = {"workload": args.workload, "seeds": len(control),
               "control_smallest_gap": min(control),
               "limits_in_force": cell.config["float_rel_limit"]}
    if sound:
        summary["program_largest_gap"] = max(s["float_rel_gap"] for s in sound)
        summary["program_exact_mismatches"] = sum(
            s["exact_mismatches"] + s["shape"] for s in sound)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
