"""Shared compute thread pool (reference: common/runtime compute runtime —
numpy/arrow kernels release the GIL, so morsel parallelism works on threads)."""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from .env import env_int

_POOL: Optional[ThreadPoolExecutor] = None
_THREAD = threading.local()


def compute_pool() -> ThreadPoolExecutor:
    global _POOL
    if _POOL is None:
        workers = env_int("DAFT_TPU_NUM_THREADS", os.cpu_count() or 4, lo=1)
        _POOL = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="daft-compute",
                                   initializer=setattr, initargs=(_THREAD, "pooled", True))
    return _POOL


def on_pool_thread() -> bool:
    """True on a thread of the compute pool. Such a caller does inline what
    it would have handed to the pool: a pool thread that waits on its own
    pool can wait for ever."""
    return getattr(_THREAD, "pooled", False)


def pool_width() -> int:
    """Threads of the compute pool: the in-flight window of a streaming scan,
    and so the width the planner keeps a scan's task list at."""
    return compute_pool()._max_workers


def pool_map(fn, items):
    """Map over items in the pool; falls back to serial for 0/1 items."""
    items = list(items)
    if len(items) <= 1:
        return [fn(x) for x in items]
    return list(compute_pool().map(fn, items))
