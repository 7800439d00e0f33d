"""A reader of `layer_metrics/` under a second name.

An accepted per-layer metric that lists its cells cannot take a new one: the
list is the benchmark's, and a PR that adds a cell edits no entry that is
there. A cell that needs the same reading adds a metric of its own whose file
is the accepted reader, loaded by path: one arithmetic, two names, nothing to
keep equal.
"""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def reader_of(metric: str):
    """`read(ctx)` of `layer_metrics/<metric>.py`."""
    path = os.path.join(HERE, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
