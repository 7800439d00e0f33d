"""Helpers of the benchmark harness's tests (see conftest.py)."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def add_cell(root, name, config, traffic, scale_factor=0.01, float_rel_limit=None):
    """A configuration file and a `workloads` entry, added to the copy."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "tpch-sf1-1chip.json")) as f:
        cfg = json.load(f)
    cfg.update(name=config, scale_factor=scale_factor)
    cfg["float_rel_limit"] = float_rel_limit or {
        "q1": 1e-5, "q6": 1e-6, "q3": 8e-6, "q5": 5e-7, "q12": 1e-6, "q19": 1e-6}
    with open(os.path.join(root, "benchmark", "configs", config + ".json"), "w") as f:
        json.dump(cfg, f)
    spec["configs"].append({"name": config, "source": "test", "reduced": ["scale_factor"],
                            "file": f"benchmark/configs/{config}.json", "why": "test"})
    spec["workloads"].append({"name": name, "config": config, "traffic": traffic,
                              "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return spec
