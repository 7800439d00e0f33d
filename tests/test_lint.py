"""Engine-invariant linter: fixture cases per rule + the tier-1 gate.

The gate test runs the whole engine over daft_tpu/ and asserts zero
non-baselined findings — the lint IS part of tier-1, so a PR that mutates a
module cache without a lock, reads an undocumented knob, or bumps an event
field without bumping SCHEMA_VERSION fails CI, not review.
"""

import json
import os
import subprocess
import sys

import pytest

from daft_tpu.tools.lint import lint, lint_source
from daft_tpu.tools.lint.engine import (ModuleContext, ProjectContext,
                                        apply_baseline, LintResult)
from daft_tpu.tools.lint.obs_rules import (check_schema_drift,
                                           event_schema_fingerprint,
                                           read_schema_version)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

UNLOCKED_CACHE = """
_CACHE = {}

def put(k, v):
    _CACHE[k] = v
"""

LOCKED_CACHE = """
import threading

_CACHE = {}
_LOCK = threading.Lock()

def put(k, v):
    with _LOCK:
        _CACHE[k] = v
"""


def test_lock_discipline_unlocked_mutation_caught():
    findings = lint_source(UNLOCKED_CACHE)
    assert "lock-discipline" in rules_of(findings)
    (f,) = [f for f in findings if f.rule == "lock-discipline"]
    assert "_CACHE" in f.message


def test_lock_discipline_locked_mutation_passes():
    assert "lock-discipline" not in rules_of(lint_source(LOCKED_CACHE))


def test_lock_discipline_import_time_population_exempt():
    src = "_CACHE = {}\n_CACHE['a'] = 1\n"  # module scope = import lock
    assert "lock-discipline" not in rules_of(lint_source(src))


def test_lock_discipline_method_mutations_and_del():
    src = """
_ITEMS = []

def f():
    _ITEMS.append(1)

def g(k):
    del _ITEMS[k]
"""
    findings = [f for f in lint_source(src) if f.rule == "lock-discipline"]
    assert len(findings) == 2


def test_lock_discipline_closure_defined_under_lock_not_credited():
    # the `with` wraps the function DEFINITION, not its execution — the
    # mutation inside the closure body runs lockless (review fix: the
    # first-parent hop used to skip the function-boundary check)
    src = """
import threading

_CACHE = {}
_LOCK = threading.Lock()

with _LOCK:
    def on_event(k, v):
        _CACHE[k] = v
"""
    assert "lock-discipline" in rules_of(lint_source(src))


def test_lock_discipline_wrong_lock_not_credited():
    src = """
import threading

_CACHE = {}

def put(self, k, v):
    with self._lock:   # instance lock cannot guard a module global
        _CACHE[k] = v
"""
    assert "lock-discipline" in rules_of(lint_source(src))


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------

def test_blocking_pickle_under_lock_caught():
    src = """
import pickle
import threading

_LOCK = threading.Lock()

def send(conn, msg):
    with _LOCK:
        buf = pickle.dumps(msg)
        conn.send_bytes(buf)
"""
    findings = [f for f in lint_source(src) if f.rule == "blocking-under-lock"]
    assert len(findings) == 2  # dumps + send_bytes


def test_blocking_outside_lock_passes():
    src = """
import pickle
import threading

_LOCK = threading.Lock()

def send(conn, msg):
    buf = pickle.dumps(msg)
    with _LOCK:
        n = len(buf)
    conn.send_bytes(buf)
"""
    assert "blocking-under-lock" not in rules_of(lint_source(src))


def test_blocking_in_nested_def_under_lock_passes():
    # defining a closure under the lock is not running it under the lock
    src = """
import pickle
import threading

_LOCK = threading.Lock()

def make(msg):
    with _LOCK:
        def later():
            return pickle.dumps(msg)
    return later
"""
    assert "blocking-under-lock" not in rules_of(lint_source(src))


def test_blocking_under_self_lock_caught():
    src = """
class W:
    def flush(self):
        with self._lock:
            open("/tmp/x", "w")
"""
    assert "blocking-under-lock" in rules_of(lint_source(src))


# ---------------------------------------------------------------------------
# env-discipline
# ---------------------------------------------------------------------------

def test_env_discipline_raw_parse_caught():
    src = """
import os

N = int(os.environ.get("DAFT_TPU_THING", 4))
F = float(os.environ.get("DAFT_TPU_OTHER", 1.5))
"""
    findings = [f for f in lint_source(src, readme_text="DAFT_TPU_THING DAFT_TPU_OTHER")
                if f.rule == "env-discipline"]
    assert len(findings) == 2
    assert "env_int" in findings[0].message


def test_env_discipline_getenv_spelling_caught():
    src = 'import os\nN = int(os.getenv("DAFT_TPU_THING", "3"))\n'
    findings = [f for f in lint_source(src, readme_text="DAFT_TPU_THING")
                if f.rule == "env-discipline"]
    assert len(findings) == 1


def test_env_discipline_helper_passes():
    src = """
from daft_tpu.utils.env import env_int

N = env_int("DAFT_TPU_THING", 4)
"""
    findings = lint_source(src, readme_text="DAFT_TPU_THING")
    assert "env-discipline" not in rules_of(findings)


# ---------------------------------------------------------------------------
# knob-registry
# ---------------------------------------------------------------------------

def test_knob_registry_undocumented_caught():
    src = 'import os\nX = os.environ.get("DAFT_TPU_SECRET_KNOB", "")\n'
    findings = [f for f in lint_source(src, readme_text="DAFT_TPU_OTHER")
                if f.rule == "knob-registry"]
    assert len(findings) == 1
    assert "DAFT_TPU_SECRET_KNOB" in findings[0].message


def test_knob_registry_documented_passes():
    src = 'import os\nX = os.environ.get("DAFT_TPU_SECRET_KNOB", "")\n'
    findings = lint_source(src, readme_text="| `DAFT_TPU_SECRET_KNOB` | ... |")
    assert "knob-registry" not in rules_of(findings)


# ---------------------------------------------------------------------------
# counter-discipline
# ---------------------------------------------------------------------------

def test_counter_discipline_undeclared_caught():
    src = """
from daft_tpu.observability.metrics import registry

def f():
    registry().inc("mystery_counter")
    registry().set_gauge("mystery_gauge", 1.0)
"""
    findings = [f for f in lint_source(src, declared_counters={"known"},
                                       declared_gauges={"g"})
                if f.rule == "counter-discipline"]
    assert len(findings) == 2


def test_counter_discipline_declared_passes():
    src = """
from daft_tpu.observability.metrics import registry

def f():
    registry().inc("known")
    registry().set_gauge_max("g", 2.0)
"""
    findings = lint_source(src, declared_counters={"known"},
                           declared_gauges={"g"})
    assert "counter-discipline" not in rules_of(findings)


def test_counter_discipline_dynamic_name_skipped():
    src = """
from daft_tpu.observability.metrics import registry

def f(k):
    registry().inc(f"shuffle_{k}", 1)
"""
    findings = lint_source(src, declared_counters=set(), declared_gauges=set())
    assert "counter-discipline" not in rules_of(findings)


def test_declared_vocabulary_collected_from_metrics_module():
    """The real metrics.py declares the vocabulary the rule checks against —
    resolved through the group-tuple names (DEVICE_COUNTER_NAMES + ...)."""
    with open(os.path.join(REPO, "daft_tpu/observability/metrics.py")) as fh:
        src = fh.read()
    ctx = ModuleContext("daft_tpu/observability/metrics.py",
                        "daft_tpu.observability.metrics", src)
    project = ProjectContext("", [ctx])
    assert "device_stage_batches" in project.declared_counters
    assert "shuffle_wire_bytes" in project.declared_counters
    assert "subscriber_errors" in project.declared_counters
    assert "hbm_bytes_resident" in project.declared_gauges


# ---------------------------------------------------------------------------
# import-discipline
# ---------------------------------------------------------------------------

def test_import_discipline_toplevel_jax_caught():
    src = "import jax\n"
    findings = lint_source(src, rel="daft_tpu/io/foo.py",
                           module="daft_tpu.io.foo")
    assert "import-discipline" in rules_of(findings)


def test_import_discipline_toplevel_tier_module_caught():
    src = "from ..ops.stage import pad_bucket\n"
    findings = lint_source(src, rel="daft_tpu/io/foo.py",
                           module="daft_tpu.io.foo")
    assert "import-discipline" in rules_of(findings)


def test_import_discipline_function_local_passes():
    src = """
def f():
    from ..ops.stage import pad_bucket
    return pad_bucket(7)
"""
    findings = lint_source(src, rel="daft_tpu/io/foo.py",
                           module="daft_tpu.io.foo")
    assert "import-discipline" not in rules_of(findings)


def test_import_discipline_tier_member_exempt():
    src = "import jax\nfrom .stage import pad_bucket\n"
    findings = lint_source(src, rel="daft_tpu/ops/grouped_stage.py",
                           module="daft_tpu.ops.grouped_stage")
    assert "import-discipline" not in rules_of(findings)


def test_import_discipline_type_checking_exempt():
    src = """
from typing import TYPE_CHECKING

if TYPE_CHECKING:
    import jax
"""
    findings = lint_source(src, rel="daft_tpu/io/foo.py",
                           module="daft_tpu.io.foo")
    assert "import-discipline" not in rules_of(findings)


# ---------------------------------------------------------------------------
# broad-except
# ---------------------------------------------------------------------------

def test_broad_except_silent_caught():
    src = """
def f():
    try:
        risky()
    except Exception:
        pass
"""
    assert "broad-except" in rules_of(lint_source(src))


@pytest.mark.parametrize("body", [
    "raise",
    "log.warning('boom')",
    "registry().inc('errors_total')",
    "return str(e)",
    "conn.send(traceback.format_exc())",
])
def test_broad_except_handled_passes(body):
    as_e = " as e" if "e" in body.split("(")[0] else ""
    src = f"""
def f():
    try:
        risky()
    except Exception{as_e}:
        {body}
"""
    assert "broad-except" not in rules_of(
        lint_source(src, declared_counters={"errors_total"}))


def test_broad_except_narrow_passes():
    src = """
def f():
    try:
        risky()
    except (OSError, ValueError):
        pass
"""
    assert "broad-except" not in rules_of(lint_source(src))


# ---------------------------------------------------------------------------
# atomic-publish
# ---------------------------------------------------------------------------

def test_atomic_publish_raw_write_caught():
    src = """
def publish(path, data):
    with open(path, "wb") as f:
        f.write(data)
"""
    findings = lint_source(src, rel="daft_tpu/distributed/shuffle.py",
                           module="daft_tpu.distributed.shuffle")
    assert "atomic-publish" in rules_of(findings)


def test_atomic_publish_tmp_then_replace_passes():
    src = """
import os

def publish(path, data):
    tmp = path + ".tmp-x"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)

def read(path):
    with open(path, "rb") as f:
        return f.read()
"""
    findings = lint_source(src, rel="daft_tpu/distributed/shuffle.py",
                           module="daft_tpu.distributed.shuffle")
    assert "atomic-publish" not in rules_of(findings)


def test_atomic_publish_os_rename_caught():
    src = "import os\n\ndef f(a, b):\n    os.rename(a, b)\n"
    findings = lint_source(src, rel="daft_tpu/checkpoint/stages.py",
                           module="daft_tpu.checkpoint.stages")
    assert "atomic-publish" in rules_of(findings)


def test_atomic_publish_other_modules_unscoped():
    src = "def f(p, d):\n    open(p, 'w').write(d)\n"
    findings = lint_source(src, rel="daft_tpu/io/foo.py",
                           module="daft_tpu.io.foo")
    assert "atomic-publish" not in rules_of(findings)


# ---------------------------------------------------------------------------
# schema-drift
# ---------------------------------------------------------------------------

EVENTS_SRC = """
from dataclasses import dataclass

@dataclass(frozen=True)
class QueryEnd:
    query_id: str
    rows: int
"""

LOG_SRC = "SCHEMA_VERSION = 3\n"


def _schema_project(events_src, log_src, pin):
    events = ModuleContext("daft_tpu/observability/events.py",
                           "daft_tpu.observability.events", events_src)
    log = ModuleContext("daft_tpu/observability/event_log.py",
                        "daft_tpu.observability.event_log", log_src)
    return ProjectContext("", [events, log], schema_pin=pin)


def test_schema_drift_in_sync_passes():
    events = ModuleContext("daft_tpu/observability/events.py",
                           "daft_tpu.observability.events", EVENTS_SRC)
    pin = {"schema_version": 3, "fingerprint": event_schema_fingerprint(events)}
    assert check_schema_drift(_schema_project(EVENTS_SRC, LOG_SRC, pin)) == []


def test_schema_drift_field_added_without_bump_caught():
    events = ModuleContext("daft_tpu/observability/events.py",
                           "daft_tpu.observability.events", EVENTS_SRC)
    pin = {"schema_version": 3, "fingerprint": event_schema_fingerprint(events)}
    grown = EVENTS_SRC + "    seconds: float\n"
    findings = check_schema_drift(_schema_project(grown, LOG_SRC, pin))
    assert [f.rule for f in findings] == ["schema-drift"]
    assert "without bumping" in findings[0].message


def test_schema_drift_bump_requires_repin():
    events = ModuleContext("daft_tpu/observability/events.py",
                           "daft_tpu.observability.events", EVENTS_SRC)
    pin = {"schema_version": 3, "fingerprint": event_schema_fingerprint(events)}
    findings = check_schema_drift(
        _schema_project(EVENTS_SRC, "SCHEMA_VERSION = 4\n", pin))
    assert [f.rule for f in findings] == ["schema-drift"]
    assert "re-pin" in findings[0].message


def test_schema_pin_matches_tree():
    """The committed schema_pin.json matches the committed event modules —
    i.e. the repo itself would pass the drift rule from a cold checkout."""
    with open(os.path.join(REPO, "daft_tpu/tools/lint/schema_pin.json")) as fh:
        pin = json.load(fh)
    with open(os.path.join(REPO, "daft_tpu/observability/events.py")) as fh:
        events = ModuleContext("daft_tpu/observability/events.py",
                               "daft_tpu.observability.events", fh.read())
    with open(os.path.join(REPO, "daft_tpu/observability/event_log.py")) as fh:
        log = ModuleContext("daft_tpu/observability/event_log.py",
                            "daft_tpu.observability.event_log", fh.read())
    assert pin["fingerprint"] == event_schema_fingerprint(events)
    assert pin["schema_version"] == read_schema_version(log)


# ---------------------------------------------------------------------------
# suppressions + baseline
# ---------------------------------------------------------------------------

def test_suppression_with_justification_honored():
    src = """
_CACHE = {}

def put(k, v):
    _CACHE[k] = v  # lint: ignore[lock-discipline] -- single-threaded tool
"""
    assert rules_of(lint_source(src)) == []


def test_suppression_standalone_comment_covers_next_code_line():
    src = """
_CACHE = {}

def put(k, v):
    # lint: ignore[lock-discipline] -- populated before any thread starts,
    # and the justification may wrap over several comment lines
    _CACHE[k] = v
"""
    assert rules_of(lint_source(src)) == []


def test_suppression_without_justification_is_a_finding():
    src = """
_CACHE = {}

def put(k, v):
    _CACHE[k] = v  # lint: ignore[lock-discipline]
"""
    assert "bad-suppression" in rules_of(lint_source(src))


def test_unused_suppression_is_a_finding():
    src = "X = 1  # lint: ignore[lock-discipline] -- nothing fires here\n"
    findings = lint_source(src)
    assert rules_of(findings) == ["bad-suppression"]
    assert "unused" in findings[0].message


def test_baseline_grandfathers_exact_count():
    findings = lint_source(UNLOCKED_CACHE)
    key = ("daft_tpu/_fixture.py", "lock-discipline")
    result = LintResult()
    kept = apply_baseline(findings, {key: {"count": 1, "why": "legacy"}}, result)
    assert kept == []
    assert result.grandfathered[key] == 1


def test_baseline_exceeded_fails():
    src = UNLOCKED_CACHE + "\ndef put2(k, v):\n    _CACHE[k] = v\n"
    findings = [f for f in lint_source(src) if f.rule == "lock-discipline"]
    assert len(findings) == 2
    result = LintResult()
    kept = apply_baseline(
        findings, {("daft_tpu/_fixture.py", "lock-discipline"):
                   {"count": 1, "why": "legacy"}}, result)
    assert len(kept) == 3  # both findings + the exceeds-baseline note
    assert any("exceed" in f.message for f in kept)


# ---------------------------------------------------------------------------
# metrics vocabulary regression (satellite): /metrics exposes every declared
# series at zero before the first increment
# ---------------------------------------------------------------------------

def test_declared_series_scrapeable_at_zero():
    from daft_tpu.observability.metrics import (DECLARED_COUNTERS,
                                                DECLARED_GAUGES,
                                                MetricsRegistry,
                                                declare_vocabulary)

    fresh = MetricsRegistry()
    declare_vocabulary(fresh)
    counters, gauges = fresh.export()
    for name in DECLARED_COUNTERS:
        assert counters.get(name) == 0, name
    for name in DECLARED_GAUGES:
        assert gauges.get(name) == 0.0, name
    # the process registry (import side effect) carries them too: the
    # previously-undeclared recovery/observability names included
    from daft_tpu.observability.metrics import registry
    snap = registry().snapshot()
    for name in ("subscriber_errors", "checkpoint_restore_failures",
                 "shuffle_fetch_server_requests", "hbm_cache_hits"):
        assert name in snap, name


def test_prometheus_text_contains_declared_series():
    from daft_tpu.observability.metrics import (MetricsRegistry,
                                                declare_vocabulary,
                                                prometheus_text)
    import daft_tpu.observability.metrics as m

    fresh = MetricsRegistry()
    declare_vocabulary(fresh)
    old = m._REGISTRY
    m._REGISTRY = fresh
    try:
        text = prometheus_text()
    finally:
        m._REGISTRY = old
    assert "daft_tpu_subscriber_errors 0" in text
    assert "daft_tpu_checkpoint_restore_failures 0" in text
    assert "# TYPE daft_tpu_hbm_bytes_resident gauge" in text


# ---------------------------------------------------------------------------
# tier-1 gate: the tree itself lints clean
# ---------------------------------------------------------------------------

def test_repo_lints_clean():
    """Zero non-baselined findings over daft_tpu/ — the acceptance gate."""
    result = lint(REPO, [os.path.join(REPO, "daft_tpu")],
                  baseline_path=os.path.join(
                      REPO, "daft_tpu/tools/lint/baseline.json"))
    msgs = "\n".join(f.render() for f in result.findings)
    assert result.ok, f"lint findings:\n{msgs}"


def test_cli_json_mode():
    """`python -m daft_tpu.tools.lint --json` exits 0 on the clean tree and
    emits the per-rule counts tooling diffs across PRs."""
    proc = subprocess.run(
        [sys.executable, "-m", "daft_tpu.tools.lint", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert isinstance(payload["counts"], dict)
    assert payload["suppressed"] > 0  # the justified escape hatches exist
