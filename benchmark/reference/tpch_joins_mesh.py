"""Plain reference of the `tpch_joins_mesh` suite: `reference/tpch_joins10.py`'s
Q3, Q5 and Q10 (numpy float64 over the same Arrow tables, joins by key
look-up, independent of `daft_tpu`), unchanged and whole: 180 M `lineitem`
rows are about 1.4 GB a float64 column, and a query's few columns and their
temporaries fit the four-chip host beside the program (the peak is printed
below, after every answer). How the program lays the rows out over its chips
is none of the reference's business.
"""

from __future__ import annotations

import importlib.util
import json
import os
import resource
from typing import Callable, Dict, Optional

import pyarrow as pa

_spec = importlib.util.spec_from_file_location(
    "bench_reference_tpch_joins10",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch_joins10.py"))
_j10 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_j10)

to_bfloat16 = _j10.to_bfloat16
TEMPLATES = {name: _j10.TEMPLATES[name] for name in ("q3", "q5", "q10")}


def answer(template: str, tables: Dict[str, pa.Table],
           storage: Optional[Callable] = None) -> Dict[str, list]:
    """The reference's answer to one template over the Arrow tables."""
    out = TEMPLATES[template](tables, storage)
    # the process's peak so far (Linux: KiB): the program's run and this answer
    print(json.dumps({"phase": "reference", "template": template,
                      "peak_rss_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}),
          flush=True)
    return out
