"""Engine-invariant policy: the configuration every lint rule reads.

One module so the invariants are stated in one place instead of scattered
through rule implementations. Each constant names a discipline the engine
already relies on (see the rule modules for the bug class each one encodes).
"""

from __future__ import annotations

PACKAGE = "daft_tpu"

# ---- lock-discipline / blocking-under-lock (concurrency.py) ------------------------

# Module-level lock factories: a name assigned one of these at module scope is
# the module's lock vocabulary for guarding its module-level mutable state.
LOCK_FACTORIES = {
    "threading.Lock", "threading.RLock", "threading.Condition",
    "Lock", "RLock", "Condition",
}

# Calls/constructors that produce a module-level mutable container.
CONTAINER_FACTORIES = {
    "dict", "list", "set",
    "OrderedDict", "collections.OrderedDict",
    "defaultdict", "collections.defaultdict",
    "deque", "collections.deque",
}

# Method calls that mutate a container in place.
MUTATOR_METHODS = {
    "append", "appendleft", "add", "setdefault", "pop", "popitem", "update",
    "clear", "extend", "insert", "remove", "discard", "move_to_end",
}

# Blocking work that must never run while a lock is held: the PR 9 bug class
# (result pickling under the heartbeat-shared send lock silenced liveness
# beats into a false-positive SIGKILL). Dotted suffixes match the END of the
# resolved call chain, attr names match the method regardless of receiver.
BLOCKING_CALL_SUFFIXES = {
    "pickle.dumps", "pickle.loads", "cloudpickle.dumps", "cloudpickle.loads",
    "time.sleep", "urllib.request.urlopen",
}
BLOCKING_ATTRS = {
    "sendall", "send_bytes", "recv", "recv_bytes", "accept", "connect",
    "device_get", "device_put", "block_until_ready", "urlopen",
    "send", "sleep",
}
BLOCKING_NAMES = {"open"}

# ---- import-discipline (config_rules.py) -------------------------------------------

# Modules whose import pays the heavy-tier price (jax import, device
# initialization, env-gated subsystems). Importing one at module top level
# from outside the tier breaks the zero-overhead contract: a host-only query
# would pay the tier's import cost (or worse, initialize a backend).
TIER_FORBIDDEN = (
    "jax",
    "daft_tpu.parallel",
    "daft_tpu.checkpoint.stages",
    "daft_tpu.ops.stage",
    "daft_tpu.ops.grouped_stage",
    "daft_tpu.ops.udf_stage",
    "daft_tpu.ops.device_join",
    "daft_tpu.ops.device_eval",
    "daft_tpu.ops.pallas_kernels",
    "daft_tpu.ops.region",
)

# Modules allowed to import the above at top level: the tier itself.
TIER_MEMBERS = (
    "daft_tpu.device",
    "daft_tpu.parallel",
    "daft_tpu.checkpoint",
    "daft_tpu.utils.jax_setup",
    "daft_tpu.ops.stage",
    "daft_tpu.ops.grouped_stage",
    "daft_tpu.ops.udf_stage",
    "daft_tpu.ops.device_join",
    "daft_tpu.ops.device_eval",
    "daft_tpu.ops.pallas_kernels",
    "daft_tpu.ops.region",
)

# ---- counter-discipline / schema-drift (obs_rules.py) ------------------------------

# The single home of the metric-name vocabulary: every literal name passed to
# registry().inc()/set_gauge()/set_gauge_max()/counters.bump() must appear in
# this module's DECLARED_COUNTERS / DECLARED_GAUGES tuples so a /metrics
# scrape of a fresh process sees every series at zero.
METRICS_MODULE = "daft_tpu/observability/metrics.py"
EVENTS_MODULE = "daft_tpu/observability/events.py"
EVENT_LOG_MODULE = "daft_tpu/observability/event_log.py"

# Handler is considered to HANDLE the exception if its body calls one of
# these (logging, counting, rejection bookkeeping), re-raises, or reads the
# bound exception at all.
EXCEPT_HANDLER_CALLS = {
    "inc", "bump", "reject", "warning", "error", "exception", "debug",
    "info", "log", "note_failure", "record", "format_exc", "print_exc",
}

# ---- env-knob discipline (config_rules.py) -----------------------------------------

ENV_HELPER_MODULE = "daft_tpu/utils/env.py"
KNOB_PREFIX = "DAFT_TPU_"
README = "README.md"

# ---- atomic-publish (publish.py) ---------------------------------------------------

# Modules that write into directories another process may concurrently read
# (shuffle map output served by the fetch server; the checkpoint store).
# Writes there must stage to a tmp/staging path and os.replace() into place.
SHARED_DIR_MODULES = (
    "daft_tpu/distributed/shuffle.py",
    "daft_tpu/checkpoint/stages.py",
)
ATOMIC_PATH_TOKENS = ("tmp", "staging")
