"""Out-of-process UDF execution.

Reference parity: daft/execution/udf.py:57 (UdfHandle: worker subprocess +
socket transport) and udf_worker.py:27 (worker loop). Workers are fresh
``python -m daft_tpu.execution._udf_worker_entry`` subprocesses connected over
a UNIX socket — NOT fork: the parent holds a multithreaded JAX runtime and
forking it risks deadlock (VERDICT r2 weak #7, the "os.fork() incompatible
with multithreaded code" warnings). The UDF closure ships to the worker via
cloudpickle (the reference vendors cloudpickle for exactly this,
daft/pickle/); batches travel as pickled Arrow arrays.

One pool per Func, sized by max_concurrency; workers are reused across
batches and shut down atexit or when the pool is garbage collected.
"""

from __future__ import annotations

import atexit
import itertools
import os
import subprocess
import sys
import tempfile
import threading
import traceback
import uuid
from multiprocessing import AuthenticationError as mp_AuthenticationError
from multiprocessing.connection import Client, Listener

from ..utils.sockets import DeadlineAcceptor
from typing import Any, Dict, List, Optional, Tuple

_POOLS: Dict[int, "UdfProcessPool"] = {}
_POOLS_LOCK = threading.Lock()


def get_pool(func) -> "UdfProcessPool":
    key = id(func)
    with _POOLS_LOCK:
        pool = _POOLS.get(key)
        if pool is None or not pool.alive:
            pool = UdfProcessPool(func)
            _POOLS[key] = pool
        return pool


def worker_main(argv: List[str]) -> None:
    """Worker entry: connect back, receive the cloudpickled UDF, serve jobs."""
    address = argv[0]
    authkey = bytes.fromhex(os.environ["DAFT_TPU_UDF_AUTHKEY"])
    conn = Client(address, family="AF_UNIX", authkey=authkey)
    try:
        conn.send(("hello", os.getpid()))
        kind, blob = conn.recv()
        assert kind == "init"
        import cloudpickle

        fn, is_batch, is_generator, is_async = cloudpickle.loads(blob)
        _worker_loop(conn, fn, is_batch, is_generator, is_async)
    finally:
        conn.close()


def _worker_loop(conn, fn, is_batch: bool, is_generator: bool, is_async: bool):
    """Receive (args_arrow, kwargs) jobs, run fn, reply."""
    from ..core.series import Series

    while True:
        try:
            msg = conn.recv()
        except (EOFError, KeyboardInterrupt):
            return
        if msg is None:
            return
        try:
            arg_arrays, names, kwargs, num_rows = msg
            series = [Series.from_arrow(a, nm) for a, nm in zip(arg_arrays, names)]
            if is_batch:
                out = fn(*series, **kwargs)
                if not isinstance(out, Series):
                    out = Series.from_pylist(list(out), "udf")
                conn.send(("ok", out.to_arrow()))
            else:
                cols = [s.to_pylist() for s in series]
                cols = [c * num_rows if len(c) == 1 and num_rows != 1 else c for c in cols]
                if is_generator:
                    results = [list(fn(*vals, **kwargs)) for vals in zip(*cols)]
                elif is_async:
                    import asyncio

                    async def run_all():
                        return await asyncio.gather(*(fn(*vals, **kwargs) for vals in zip(*cols)))

                    results = asyncio.run(run_all())
                else:
                    results = [fn(*vals, **kwargs) for vals in zip(*cols)]
                conn.send(("ok", results))
        except Exception:
            conn.send(("err", traceback.format_exc()))


class UdfProcessPool:
    def __init__(self, func):
        import cloudpickle

        self.func = func
        n = func.max_concurrency or 1
        sock = os.path.join(tempfile.gettempdir(),
                            f"daft_tpu_udf_{os.getpid()}_{uuid.uuid4().hex[:8]}.sock")
        # HMAC-authenticated socket: the listener unpickles only from processes
        # holding the per-pool secret (passed via the child's environment)
        authkey = os.urandom(32)
        self._listener = Listener(sock, family="AF_UNIX", authkey=authkey)
        blob = cloudpickle.dumps(
            (func.fn, func.is_batch, getattr(func, "is_generator", False), func.is_async))
        env = dict(os.environ)
        env.setdefault("DAFT_TPU_DEVICE", "off")
        if env["DAFT_TPU_DEVICE"] == "off":
            env["JAX_PLATFORMS"] = "cpu"  # the chip stays with the parent
        env["DAFT_TPU_UDF_AUTHKEY"] = authkey.hex()
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = pkg_root + (os.pathsep + prev if prev else "")

        # spawn every worker first, then collect connections: pool startup is
        # one interpreter cold-start, not max_concurrency of them in series
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "daft_tpu.execution._udf_worker_entry", sock],
                env=env)
            for _ in range(n)
        ]
        self.workers: List[Tuple[Any, Any]] = []  # (Popen, conn)
        self._closed = False
        by_pid = {p.pid: p for p in procs}

        def _cleanup_and_raise(msg):
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            try:
                self._listener.close()
            except OSError:
                pass
            raise RuntimeError(msg)

        conns = []
        acceptor = DeadlineAcceptor(self._listener)
        deadline = 120.0
        while len(conns) < n:
            try:
                conn = acceptor.accept(0.5)
            except mp_AuthenticationError:
                conn = None  # stranger with the wrong key
            if conn is not None:
                conns.append(conn)
                continue
            dead = [p for p in procs if p.poll() is not None]
            if len(dead) > n - len(conns) - 1:
                _cleanup_and_raise(
                    f"UDF worker for {func.name!r} exited with "
                    f"code {dead[0].returncode} before connecting")
            deadline -= 0.5
            if deadline <= 0:
                _cleanup_and_raise("UDF workers never connected (120s)")
        for conn in conns:
            try:
                if not conn.poll(30):
                    _cleanup_and_raise("UDF worker never sent hello")
                hello = conn.recv()
                assert hello[0] == "hello", hello
                conn.send(("init", blob))
            except (EOFError, BrokenPipeError, ConnectionError, OSError):
                _cleanup_and_raise(
                    f"UDF worker for {func.name!r} died during handshake")
            # pair connection with ITS process via the hello pid (accept order
            # is arrival order, not spawn order)
            proc = by_pid.get(hello[1])
            self.workers.append((proc, conn))
        self._rr = itertools.cycle(range(n))
        self._locks = [threading.Lock() for _ in range(n)]
        self.alive = True
        atexit.register(self.shutdown)

    def run_batch_routed(self, arg_series: List[Any], kwargs: dict,
                         num_rows: int, prefix_len: int):
        """Prefix-affinity dispatch (reference: the vLLM pipeline node's
        prefix-aware routed actor pool, src/daft-distributed/src/pipeline_node/
        vllm.rs): rows whose first `prefix_len` chars of the FIRST argument
        match route to the same replica, so each replica's KV/prompt cache
        keeps serving its prefix family. Sub-batches run on their replicas
        CONCURRENTLY; results reassemble in input row order."""
        import zlib

        import numpy as np

        n_workers = len(self.workers)
        if n_workers <= 1 or num_rows <= 1:
            return self.run_batch(arg_series, kwargs, num_rows)
        keys = arg_series[0].to_pylist()
        # crc32: a STABLE hash — builtin hash() is salted per process
        # (PYTHONHASHSEED), which would re-shuffle prefix->replica affinity on
        # every driver restart and lose long-lived replicas' KV caches. str()
        # coerces non-string first args (ints, dates) instead of raising.
        assign = np.asarray(
            [zlib.crc32(str(k if k is not None else "")[:prefix_len]
                        .encode("utf-8", "surrogatepass")) % n_workers
             for k in keys],
            dtype=np.int64)
        groups = [np.flatnonzero(assign == w) for w in range(n_workers)]
        from concurrent.futures import ThreadPoolExecutor

        def run_one(w: int, rows: np.ndarray):
            sub = [s.take(rows) for s in arg_series]
            return self._dispatch(w, sub, kwargs, len(rows))

        with ThreadPoolExecutor(max_workers=n_workers) as ex:
            futures = {w: ex.submit(run_one, w, rows)
                       for w, rows in enumerate(groups) if len(rows)}
            payloads = {w: f.result() for w, f in futures.items()}
        # reassemble: payload is an arrow array (batch fn) or a list (row fn)
        first = next(iter(payloads.values()))
        if isinstance(first, list):
            out: List[Any] = [None] * num_rows
            for w, rows in enumerate(groups):
                if not len(rows):
                    continue
                for j, r in enumerate(rows):
                    out[int(r)] = payloads[w][j]
            return out
        import pyarrow as pa

        chunks = []
        order = []
        for w, rows in enumerate(groups):
            if not len(rows):
                continue
            arr = payloads[w]
            if isinstance(arr, pa.ChunkedArray):
                arr = arr.combine_chunks()
            chunks.append(arr)
            order.append(rows)
        combined = pa.concat_arrays(chunks) if len(chunks) > 1 else chunks[0]
        perm = np.concatenate(order)
        inv = np.empty(num_rows, dtype=np.int64)
        inv[perm] = np.arange(num_rows)
        return combined.take(pa.array(inv))

    def _dispatch(self, i: int, arg_series: List[Any], kwargs: dict,
                  num_rows: int):
        p, conn = self.workers[i]
        with self._locks[i]:
            if p is not None and p.poll() is not None:
                raise RuntimeError(f"UDF worker process for {self.func.name!r} died")
            try:
                conn.send((
                    [s.to_arrow() for s in arg_series],
                    [s.name for s in arg_series],
                    kwargs,
                    num_rows,
                ))
                status, payload = conn.recv()
            except (EOFError, BrokenPipeError, ConnectionError, OSError) as e:
                self.shutdown()
                raise RuntimeError(
                    f"UDF worker for {self.func.name!r} died mid-batch "
                    f"(crash in the UDF or native code?): {e}") from e
        if status == "err":
            raise RuntimeError(f"UDF {self.func.name!r} failed in worker:\n{payload}")
        return payload

    def run_batch(self, arg_series: List[Any], kwargs: dict, num_rows: int):
        """Dispatch one batch to a worker; returns arrow array (batch fn) or
        a python list of results (row fn)."""
        i = next(self._rr)
        p, conn = self.workers[i]
        with self._locks[i]:
            if p is not None and p.poll() is not None:
                raise RuntimeError(f"UDF worker process for {self.func.name!r} died")
            try:
                conn.send((
                    [s.to_arrow() for s in arg_series],
                    [s.name for s in arg_series],
                    kwargs,
                    num_rows,
                ))
                status, payload = conn.recv()
            except (EOFError, BrokenPipeError, ConnectionError, OSError) as e:
                # segfault/OOM-kill mid-batch: surface WHICH udf died; tear the
                # whole pool down (surviving workers, listener, socket) so the
                # next dispatch builds a fresh one with nothing leaked
                self.shutdown()
                raise RuntimeError(
                    f"UDF worker for {self.func.name!r} died mid-batch "
                    f"(crash in the UDF or native code?): {e}") from e
        if status == "err":
            raise RuntimeError(f"UDF {self.func.name!r} failed in worker:\n{payload}")
        return payload

    def shutdown(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.alive = False
        for p, conn in self.workers:
            try:
                conn.send(None)
                conn.close()
            except Exception:  # lint: ignore[broad-except] -- shutdown: peer may already be gone
                pass
        for p, _ in self.workers:
            if p is None:
                continue
            try:
                p.wait(timeout=2)
            except subprocess.TimeoutExpired:
                p.terminate()
        try:
            self._listener.close()
        except OSError:
            pass
