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


def _compile_span(event: str, seconds: float, **_kw) -> None:
    """JAX's own report of one XLA compilation, as an `xla.compile` span
    (end = now) while a SpanRecorder is installed: a timeline then shows
    which dispatch recompiled. Off, the listener is one recorder look-up."""
    if event != "/jax/core/compile/backend_compile_duration":
        return
    from ..observability import runtime_stats

    if runtime_stats.current_spans() is not None:
        now = time.time()
        runtime_stats.record_span("xla.compile", "compile", now - seconds, now)


jax.monitoring.register_event_duration_secs_listener(_compile_span)


def get_jax():
    return jax
