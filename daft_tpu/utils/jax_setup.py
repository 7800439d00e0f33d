"""Central JAX configuration for the engine.

Import this module before any device work. Enables 64-bit mode: a data engine's
aggregation semantics (int64 sums, float64 means) require x64; compute-heavy kernels
opt into bf16/f32 explicitly where precision allows (SURVEY.md §7 MXU notes).
"""

from __future__ import annotations

import os
import time

import jax
import jax.monitoring

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache, placed from outside: where
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and this module sets
# nothing; where it is not, the cache lives at one fixed path inside the
# checkout (the path is part of the cache key, so a directory that moves never
# hits). A failure to set it is an error, not a silent cold start.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """Resolved persistent-compile-cache directory."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)


# JAX's own reports of what building a program cost: tracing the Python
# function to a jaxpr, lowering the jaxpr to an MLIR module, and XLA's
# compilation (a persistent-cache hit costs its look-up and retrieval).
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("xla.trace", "jax_trace_us"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("xla.lower", "jax_lower_us"),
    "/jax/core/compile/backend_compile_duration": ("xla.compile", "xla_compile_us"),
}


def _build_report(event: str, seconds: float, **_kw) -> None:
    """One report of `_BUILD_EVENTS`, heard as the work ends: its counter
    always (the listener fires only where something is traced or compiled,
    never in a warm query), and a leaf span of its whole extent (end = now)
    under the context's open span while a SpanRecorder is installed: a
    timeline then shows which dispatch built a program.

    The reports nest (a jitted `jnp` function traced inside a stage program's
    trace reports first, then the program's own trace reports an extent that
    holds it; a constant computed eagerly in a trace compiles), and a cold
    span can lie inside one or around one, so a counter takes its extent's
    self time (`runtime_stats.cold_self_seconds`): every second is counted
    once, for the innermost site, and the counters add up."""
    site = _BUILD_EVENTS.get(event)
    if site is None:
        return
    from ..observability import runtime_stats
    from ..observability.metrics import registry

    name, counter = site
    now = time.time()
    start = now - seconds
    registry().inc(counter, int(runtime_stats.cold_self_seconds(start, now) * 1e6))
    if runtime_stats.current_spans() is not None:
        runtime_stats.record_span(name, "compile", start, now)


jax.monitoring.register_event_duration_secs_listener(_build_report)


def get_jax():
    return jax
