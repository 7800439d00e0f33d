"""Series: a named, typed column of values.

Reference parity: src/daft-core/src/series/mod.rs:32 (Series over SeriesLike) and the
~65 kernels under src/daft-core/src/array/ops/. Our host storage is a pyarrow.Array
(Arrow semantics for nulls: kernels propagate nulls); device storage is a
(values, validity) pair of jax Arrays produced by ``to_device()``.

Kernels lean on pyarrow.compute for host execution — analogous to the reference
leaning on arrow-rs compute — with numpy fallbacks. Device kernels live in
daft_tpu.ops and are reached through the stage compiler, not through Series.
"""

from __future__ import annotations

import hashlib
from typing import Any, Iterator, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..datatype import DataType, Field


def _combine(arr) -> pa.Array:
    if isinstance(arr, pa.ChunkedArray):
        # combine_chunks copies even ONE chunk (at SF10 the seven TPC-H
        # tables the join cell loads were 10.7 GB twice over, PR 38): a
        # single chunk that starts at its buffers' start is the column
        if arr.num_chunks == 1 and arr.chunk(0).offset == 0:
            return arr.chunk(0)
        return arr.combine_chunks()
    return arr


class Series:
    # _device_cache holds small HOST-side memo values only (dictionary-reject
    # markers, distinct-count estimates); device-resident buffers live in the
    # process-wide HBM residency manager (daft_tpu/device/residency.py), keyed
    # by _rtoken — a monotonic identity token that, unlike id(), is never
    # reused after GC. __weakref__ lets the manager drop entries when the
    # Series dies. _root/_roff are the lineage of a zero-copy view (slice):
    # the unsliced column it views and the row it starts at. The manager keys
    # a view's slots on (root, offset, length), so a fresh morsel of a
    # resident column finds what its rows built on the last query.
    __slots__ = ("_name", "_dtype", "_arrow", "_pyobjs", "_device_cache",
                 "_dict_codes", "_rtoken", "_root", "_roff", "__weakref__")

    def __init__(self, name: str, dtype: DataType, arrow: Optional[pa.Array], pyobjs: Optional[list] = None):
        self._name = name
        self._dtype = dtype
        self._arrow = arrow
        self._pyobjs = pyobjs  # only for DataType.python()

    # ---- constructors -------------------------------------------------------------
    @classmethod
    def from_arrow(cls, arr, name: str = "series", dtype: Optional[DataType] = None) -> "Series":
        arr = _combine(arr)
        encoded = None
        if pa.types.is_dictionary(arr.type):
            encoded = arr
            arr = arr.dictionary_decode()
        inferred = DataType.from_arrow(arr.type)
        if dtype is None:
            dtype = inferred
        # normalize storage (e.g. string -> large_string) so downstream kernels see one repr
        target = dtype.to_arrow() if not dtype.is_python() else None
        if target is not None and arr.type != target:
            arr = arr.cast(target)
        out = cls(name, dtype, arr)
        if encoded is not None and dtype == inferred:
            out._keep_dictionary(encoded)
        return out

    def _keep_dictionary(self, encoded: "pa.DictionaryArray") -> None:
        """A column that arrives dictionary-encoded (a Parquet reader asked
        for it so, `io/parquet.py`) keeps the encoding as its `dict_codes`:
        the integer codes are renumbered to first-occurrence order, which
        costs a fifth of hashing the strings again. Strings and binary
        without nulls only, as `_arrow_dict_codes`."""
        if encoded.null_count or encoded.dictionary.null_count \
                or not (self._dtype.is_string() or self._dtype.is_binary()):
            return
        import pandas as pd

        codes, used = pd.factorize(encoded.indices.to_numpy(zero_copy_only=False))
        values = encoded.dictionary.take(pa.array(used)).to_pylist()
        if len(set(values)) != len(values):
            return  # a dictionary that repeats a value is no encoding to keep
        self._dict_codes = (codes.astype(np.int32, copy=False), values, len(values))

    @classmethod
    def from_pylist(cls, data: Sequence[Any], name: str = "series", dtype: Optional[DataType] = None) -> "Series":
        if dtype is not None and dtype.is_python():
            return cls(name, dtype, None, list(data))
        if dtype is not None:
            arr = pa.array(data, type=dtype.to_arrow())
            return cls(name, dtype, arr)
        try:
            arr = pa.array(data)
        except (pa.ArrowInvalid, pa.ArrowTypeError, pa.ArrowNotImplementedError):
            return cls(name, DataType.python(), None, list(data))
        return cls.from_arrow(arr, name)

    @classmethod
    def from_numpy(cls, arr: np.ndarray, name: str = "series", dtype: Optional[DataType] = None) -> "Series":
        if arr.dtype == object:
            return cls.from_pylist(list(arr), name, dtype)
        if arr.ndim == 2:
            # 2D numpy -> fixed-size-list / embedding-style column
            inner = DataType.from_arrow(pa.from_numpy_dtype(arr.dtype))
            dt = dtype or DataType.fixed_size_list(inner, arr.shape[1])
            flat = pa.array(arr.reshape(-1))
            fsl = pa.FixedSizeListArray.from_arrays(flat, arr.shape[1])
            return cls.from_arrow(fsl, name, dt)
        pa_arr = pa.array(arr)
        s = cls.from_arrow(pa_arr, name)
        if dtype is not None and s._dtype != dtype:
            s = s.cast(dtype)
        return s

    @classmethod
    def empty(cls, name: str, dtype: DataType) -> "Series":
        if dtype.is_python():
            return cls(name, dtype, None, [])
        return cls(name, dtype, pa.array([], type=dtype.to_arrow()))

    @classmethod
    def full_null(cls, name: str, dtype: DataType, length: int) -> "Series":
        if dtype.is_python():
            return cls(name, dtype, None, [None] * length)
        return cls(name, dtype, pa.nulls(length, type=dtype.to_arrow()))

    # ---- basic accessors ----------------------------------------------------------
    @property
    def name(self) -> str:
        return self._name

    @property
    def dtype(self) -> DataType:
        return self._dtype

    def field(self) -> Field:
        return Field(self._name, self._dtype)

    def __len__(self) -> int:
        if self._pyobjs is not None:
            return len(self._pyobjs)
        return len(self._arrow)

    def __iter__(self) -> Iterator[Any]:
        return iter(self.to_pylist())

    def __repr__(self) -> str:
        vals = self.to_pylist()
        preview = ", ".join(repr(v) for v in vals[:8])
        if len(vals) > 8:
            preview += ", …"
        return f"Series[{self._name}: {self._dtype}; {len(self)}]([{preview}])"

    def rename(self, name: str) -> "Series":
        return Series(name, self._dtype, self._arrow, self._pyobjs)

    def null_count(self) -> int:
        if self._pyobjs is not None:
            return sum(1 for v in self._pyobjs if v is None)
        return self._arrow.null_count

    # ---- conversion ---------------------------------------------------------------
    def to_arrow(self) -> pa.Array:
        if self._pyobjs is not None:
            raise ValueError(f"Series {self._name!r} holds Python objects; no arrow representation")
        return self._arrow

    def to_pylist(self) -> list:
        if self._pyobjs is not None:
            return list(self._pyobjs)
        if self._dtype.kind in ("embedding", "fixed_shape_tensor", "fixed_shape_image"):
            np_vals = self.to_numpy()
            valid = self.validity_numpy()
            return [np_vals[i] if valid[i] else None for i in range(len(self))]
        return self._arrow.to_pylist()

    def to_numpy(self) -> np.ndarray:
        """Dense numpy values. Nulls become 0/NaN; consult validity_numpy() for the mask."""
        if self._pyobjs is not None:
            return np.array(self._pyobjs, dtype=object)
        arr = self._arrow
        dt = self._dtype
        if dt.kind in ("embedding", "fixed_shape_tensor", "fixed_shape_image", "fixed_size_list"):
            if dt.kind == "fixed_shape_image":
                inner_np = np.dtype(
                    __import__("daft_tpu.datatype", fromlist=["ImageMode"]).ImageMode.np_dtype(dt.params[0])
                )
                shape = dt.shape
            elif dt.kind == "fixed_shape_tensor":
                inner_np, shape = dt.inner.to_numpy(), dt.shape
            else:
                inner_np, shape = dt.inner.to_numpy(), (dt.size,)
            # .values keeps child slots under null rows (dense); .flatten() drops them
            flat = arr.values if hasattr(arr, "values") else arr.flatten()
            values = np.asarray(flat.to_numpy(zero_copy_only=False), dtype=inner_np)
            if flat.null_count:
                values = np.nan_to_num(values) if values.dtype.kind == "f" else values
            n_expect = len(arr) * int(np.prod(shape))
            if len(values) != n_expect:
                # ragged child (some arrow paths drop null slots): rebuild dense
                dense = np.zeros(n_expect, dtype=inner_np)
                valid = self.validity_numpy()
                per = int(np.prod(shape))
                flat_vals = np.asarray(arr.flatten().to_numpy(zero_copy_only=False), dtype=inner_np)
                pos = 0
                for i, v in enumerate(valid):
                    if v:
                        dense[i * per:(i + 1) * per] = flat_vals[pos:pos + per]
                        pos += per
                values = dense
            return values.reshape((len(arr),) + tuple(shape))
        if dt.is_boolean():
            return np.asarray(arr.to_numpy(zero_copy_only=False), dtype=bool)
        if dt.is_string() or dt.is_binary() or dt.is_nested() or dt.is_logical():
            return np.asarray(arr.to_numpy(zero_copy_only=False))
        np_dtype = dt.to_numpy()
        if arr.null_count:
            fill = 0 if np_dtype.kind in "iub" else np.nan
            arr = arr.fill_null(_null_fill_scalar(arr.type, fill))
        out = arr.to_numpy(zero_copy_only=False)
        return np.asarray(out).astype(np_dtype, copy=False)

    def validity_numpy(self) -> np.ndarray:
        if self._pyobjs is not None:
            return np.array([v is not None for v in self._pyobjs], dtype=bool)
        if self._arrow.null_count == 0:
            return np.ones(len(self._arrow), dtype=bool)
        return np.asarray(pc.is_valid(self._arrow).to_numpy(zero_copy_only=False), dtype=bool)

    def to_device(self, pad_to: Optional[int] = None, f32: bool = False):
        """(values, validity) as jax Arrays, optionally padded to ``pad_to`` rows.

        Padding rows are marked invalid; this is the padding+masking convention the
        stage compiler uses to keep XLA shapes static (SURVEY.md §7 'hard parts').

        ``f32=True`` downcasts float64 columns to float32 — the engine's device
        compute dtype. TPU f64 is software-emulated (~5x slower, measured) and
        halving the column bytes doubles effective HBM residency + h2d bandwidth;
        aggregations recover accuracy by combining per-chunk partials in f64
        (see ops/grouped_stage.py).
        """
        from ..utils import jax_setup  # noqa: F401  (enables x64 before device use)
        import jax.numpy as jnp

        return self._upload(pad_to, f32, jnp.asarray)

    def _upload(self, pad_to: Optional[int], f32: bool, put):
        """The one upload body behind to_device and to_device_sharded: the
        padded host planes, then `put` on each (the layout's placement): a
        column at a time and a transfer a plane, which is how a plane that
        stays resident arrives (a streamed morsel's planes
        go together: ops/stage.batch_planes). A `device.upload` span with
        `device.upload.prepare` inside it while a recorder is installed; the
        host's time in both is always counted (`h2d_upload_us`,
        `h2d_prepare_us`: cold sites, so set-up can be read from counters
        alone; the put is their difference)."""
        from ..observability.runtime_stats import timed_span

        with timed_span("device.upload", "device", counter="h2d_upload_us",
                        rows=len(self), dtype=str(self._dtype)) as sp:
            values, validity = self._prepared_planes(pad_to, f32)
            sp.args["bytes"] = int(values.nbytes) + int(validity.nbytes)
            out = put(values), put(validity)
        note_upload(transfers=2, planes=2)
        return out

    def _prepared_planes(self, pad_to: Optional[int], f32: bool):
        """`_padded_planes` as `_upload` calls it: the span
        `device.upload.prepare` (what making the host planes of this column
        cost: Arrow to numpy, the float32 cast, the pad, the validity plane)
        and its counter `h2d_prepare_us`, a part of the upload's own."""
        from ..observability.runtime_stats import timed_span

        with timed_span("device.upload.prepare", "host",
                        counter="h2d_prepare_us", part=True, rows=len(self),
                        dtype=str(self._dtype), pad_to=pad_to) as sp:
            values, validity = self._padded_planes(pad_to, f32)
            sp.args["bytes"] = int(values.nbytes) + int(validity.nbytes)
        return values, validity

    def _padded_planes(self, pad_to: Optional[int], f32: bool,
                       own_validity: bool = True):
        """Host-side (values, validity) numpy planes padded to `pad_to` rows
        (padding invalid), with the h2d byte attribution every device
        placement shares, so padding and accounting can never drift between
        layouts. With `own_validity` false a column without nulls gives None
        for its validity plane and counts no byte for it: the caller has that
        plane on the device already (rows valid, padding not: a dispatch's
        row mask)."""
        values = self.to_numpy()
        if f32 and values.dtype == np.float64:
            values = values.astype(np.float32)
        validity = self.validity_numpy() \
            if own_validity or self.null_count() else None
        if pad_to is not None and pad_to > len(self):
            pad = pad_to - len(self)
            pad_shape = (pad,) + values.shape[1:]
            values = np.concatenate([values, np.zeros(pad_shape, dtype=values.dtype)])
            if validity is not None:
                validity = np.concatenate([validity, np.zeros(pad, dtype=bool)])
        from ..observability.metrics import registry

        # h2d attribution: a fully-resident repeat query shows a zero delta
        registry().inc("hbm_h2d_bytes", int(values.nbytes)
                       + (int(validity.nbytes) if validity is not None else 0))
        return values, validity

    def to_device_sharded(self, mesh, pad_to: int, f32: bool = False,
                          axis: str = "dp"):
        """(values, validity) placed row-sharded over a device mesh
        (NamedSharding along `axis`): each device holds a contiguous row shard
        in its own HBM, so a mesh stage reads its shard locally with zero
        repartition. `pad_to` must be a multiple of the mesh size (padding
        rows are invalid, same convention as to_device)."""
        from ..utils import jax_setup  # noqa: F401
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        n_dev = mesh.shape[axis]
        if pad_to % n_dev != 0:
            raise ValueError(
                f"to_device_sharded: pad_to={pad_to} not divisible by the "
                f"{n_dev}-device mesh")
        sharding = NamedSharding(mesh, PartitionSpec(axis))
        return self._upload(pad_to, f32, lambda a: jax.device_put(a, sharding))

    @staticmethod
    def plane_slot(pad_to: Optional[int], f32: bool) -> tuple:
        """The residency slot key of a column's (values, validity) planes on
        one device."""
        return ("col", pad_to, bool(f32))

    def to_device_cached(self, pad_to: Optional[int] = None, f32: bool = False,
                         mesh=None, axis: str = "dp"):
        """to_device through the process-wide HBM residency manager.

        Collected tables queried repeatedly keep their columns resident in HBM
        (GPU-database-style column cache), so only the first query pays the
        host->device transfer. Series is immutable, so the cached plane never
        stales; the manager evicts it LRU under the DAFT_TPU_HBM_BUDGET.

        With `mesh`, the plane is placed row-sharded over the mesh
        (to_device_sharded) and cached under a slot key carrying the sharding
        spec — mesh and single-chip layouts of the same column are distinct
        residency entries (different physical placement), each with honest
        per-device byte accounting, and sharded slots publish in the worker
        heartbeat digest like any other deps-free plane."""
        from ..device.residency import manager

        if mesh is None:
            return manager().get_or_build(
                self, self.plane_slot(pad_to, f32), (),
                lambda: self.to_device(pad_to, f32=f32))
        key = ("col", pad_to, bool(f32), "mesh", int(mesh.shape[axis]), axis)
        return manager().get_or_build(
            self, key, (),
            lambda: self.to_device_sharded(mesh, pad_to, f32=f32, axis=axis))

    def __getstate__(self):
        """Pickle for cross-process shipping (distributed tasks/UDF workers):
        device residency, dictionary caches and a view's lineage are
        process-local — drop them."""
        return (self._name, self._dtype, self._arrow, self._pyobjs)

    def __setstate__(self, state):
        name, dtype, arrow, pyobjs = state
        object.__setattr__(self, "_name", name)
        object.__setattr__(self, "_dtype", dtype)
        object.__setattr__(self, "_arrow", arrow)
        object.__setattr__(self, "_pyobjs", pyobjs)

    def is_device_resident(self, pad_to: Optional[int] = None, f32: bool = False,
                           mesh_devices: int = 0, axis: str = "dp") -> bool:
        """True if this column is already in HBM for the given layout (cost-model
        hook — resident inputs are costed with zero transfer bytes).
        mesh_devices > 0 probes the row-sharded mesh layout instead."""
        from ..device.residency import manager

        if mesh_devices > 0:
            return manager().is_resident(
                self, ("col", pad_to, bool(f32), "mesh", int(mesh_devices), axis))
        return manager().is_resident(self, self.plane_slot(pad_to, f32))

    def content_fingerprint(self) -> Optional[int]:
        """64-bit CONTENT hash of this column (dtype + length + values +
        validity; the name is excluded — device planes depend only on data).

        Unlike ``_rtoken`` (process-local identity), the fingerprint is a pure
        function of the data: the driver and a worker that unpickled a copy
        compute the SAME value independently, so residency slot keys derived
        from it are stable across processes and across re-unpickled sub-plans
        (distributed cache-affinity scheduling + worker-side slot rebinding,
        device/residency.py). Cached in ``_device_cache`` (dropped on pickle,
        recomputed on demand). None = no stable identity (python-object
        columns, hash failure) — callers degrade to identity-only caching."""
        cache = getattr(self, "_device_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_device_cache", cache)
        fp = cache.get("__content_fp__")
        if fp is not None:
            return fp
        if self._pyobjs is not None or self._arrow is None:
            return None
        from ..observability.runtime_stats import timed_span

        # reads the whole column, once: a cold site (`content_hash_us`)
        with timed_span("series.fingerprint", "host", counter="content_hash_us",
                        rows=len(self), dtype=str(self._dtype)):
            fp = self._hash_content()
        if fp is not None:
            cache["__content_fp__"] = fp
        return fp

    def _hash_content(self) -> Optional[int]:
        """`content_fingerprint`'s hash of an Arrow-backed column; None
        where it cannot be hashed. dtype, length, how many byte buffers hold
        the column (`_content_buffers`: one more where there are nulls), then
        each buffer's length and the digests of its `_HASH_CHUNK_BYTES` pieces
        in order: a function of the bytes alone, whoever hashes the pieces.
        Over one chunk they go to the compute pool (hashlib releases the
        interpreter's lock), unless this thread is the pool's own: a pool
        thread never waits on its pool. The clock and the counters are the
        caller's."""
        from ..observability.metrics import registry
        from ..utils.pool import compute_pool, on_pool_thread

        h = hashlib.blake2b(digest_size=8)
        h.update(repr(self._dtype).encode())
        h.update(len(self).to_bytes(8, "little"))
        try:
            buffers, inplace = self._content_buffers()
        except Exception:  # lint: ignore[broad-except] -- unhashable: no content fingerprint,
            return None  # caller keys by identity instead
        views = [_byte_view(b) for b in buffers]
        nbytes = sum(len(v) for v in views)
        pieces = [v[i:i + _HASH_CHUNK_BYTES] for v in views
                  for i in range(0, len(v), _HASH_CHUNK_BYTES)]
        if nbytes <= _HASH_CHUNK_BYTES or on_pool_thread():
            digests = map(_chunk_digest, pieces)
        else:
            digests = compute_pool().map(_chunk_digest, pieces)
        h.update(len(views).to_bytes(8, "little"))
        for v in views:
            h.update(len(v).to_bytes(8, "little"))
        for d in digests:
            h.update(d)
        reg = registry()
        reg.inc("content_hash_bytes", nbytes)
        reg.inc("content_hash_inplace" if inplace else "content_hash_copied")
        return int.from_bytes(h.digest(), "little")

    def _content_buffers(self):
        """(buffers, in place) of this Arrow-backed column for `_hash_content`:
        what holds its values and validity, independent of the physical
        layout (a slice, a pickle round trip and a fresh copy of the same rows
        give equal bytes). In place, nothing copied: the value buffer of a
        fixed-width column and the offsets and data of a large_string or
        large_binary, cut to the array's offset and length, where the column
        has no nulls (validity is then no buffer at all). Normalised through a
        copy first: a string slice's offsets, rebased to its first (pickling
        rebases them too); a column with nulls, whose null slots may hold
        anything (filled, and its validity bits packed); booleans, fixed-shape
        and nested types (today's dense values, or the Arrow IPC stream:
        equal arrays in unusual layouts may then differ, which costs a missed
        rebind, never correctness)."""
        arr = self._arrow
        n = len(arr)
        if n == 0:
            return [], True
        t = arr.type
        validity = [np.packbits(self.validity_numpy())] if arr.null_count else []
        if pa.types.is_large_string(t) or pa.types.is_large_binary(t):
            if validity:
                arr = arr.fill_null("" if pa.types.is_large_string(t) else b"")
            _, offsets, data = arr.buffers()
            offsets = np.frombuffer(offsets, np.int64, n + 1, arr.offset * 8)
            first, last = int(offsets[0]), int(offsets[-1])
            if first:
                offsets = offsets - first
            data = memoryview(data)[first:last] if last > first else b""
            return [offsets, data] + validity, not (first or validity)
        if pa.types.is_primitive(t) and t.bit_width % 8 == 0 and not validity:
            width = t.bit_width // 8
            values = memoryview(arr.buffers()[1])
            return [values[arr.offset * width:(arr.offset + n) * width]], True
        try:
            vals = self.to_numpy()  # nulls filled: 0, NaN
        except Exception:  # lint: ignore[broad-except] -- no dense form: the Arrow IPC stream below
            vals = None
        if vals is not None and vals.dtype != object:
            return [vals] + validity, False
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, pa.schema([pa.field("c", t)])) as w:
            w.write_batch(pa.record_batch([arr], names=["c"]))
        return [sink.getvalue()], False

    def dict_codes(self):
        """Dictionary-encode this column: (codes int32 ndarray, values list, K).

        codes[i] in [0, K): index of row i's value in ``values`` (first-occurrence
        order); nulls get their own code. Cached on the Series (immutable), so
        repeated grouped queries over a resident table factorize each key column
        exactly once — the device grouped-agg stage combines per-column codes into
        segment ids ON DEVICE instead of re-factorizing rows per query
        (reference contrast: daft-groupby make_groups runs per batch).
        """
        cached = getattr(self, "_dict_codes", None)
        if cached is not None:
            return cached
        from ..observability.runtime_stats import timed_span
        from .kernels.groupby import make_groups

        # first touch of a key column, a cold site: a span while a recorder
        # is installed, the host's time always (`dict_encode_us`)
        with timed_span("series.dict_encode", "host", counter="dict_encode_us",
                        rows=len(self)) as sp:
            fast = self._arrow_dict_codes()
            if fast is not None:
                codes, values = fast
            else:
                first_idx, group_ids, _ = make_groups([self])
                codes = group_ids.astype(np.int32, copy=False)
                values = self.take(first_idx).to_pylist()
            sp.args["cardinality"] = len(values)
        out = (codes, values, len(values))
        object.__setattr__(self, "_dict_codes", out)
        return out

    def arrow_encodes(self) -> bool:
        """Whether `dict_codes` of this column is Arrow's own dictionary-encode
        (`_arrow_dict_codes`): strings and binary without nulls."""
        dt = self._dtype
        return self._pyobjs is None and (dt.is_string() or dt.is_binary()) \
            and not self._arrow.null_count

    def _arrow_dict_codes(self):
        """(codes int32, values) of a string or binary column without nulls,
        straight from Arrow's hash dictionary-encode, whose dictionary is in
        first-occurrence order as `dict_codes` promises: the general path
        (`make_groups`) widens the same codes to int64, factorizes them again
        and takes the firsts back out, twice the time for nothing here (a
        streamed scan encodes its group keys once a morsel). None where the
        general path has to run."""
        if not self.arrow_encodes():
            return None
        arr = self._arrow
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        de = arr.dictionary_encode()
        codes = de.indices.to_numpy(zero_copy_only=False).astype(np.int32, copy=False)
        return codes, de.dictionary.to_pylist()

    # ---- selection kernels --------------------------------------------------------
    def slice(self, start: int, end: int) -> "Series":
        if self._pyobjs is not None:
            return Series(self._name, self._dtype, None, self._pyobjs[start:end])
        out = Series(self._name, self._dtype, self._arrow.slice(start, end - start))
        # a zero-copy view remembers what it views: a slice of a slice
        # composes to the same root (python-object columns copy: no lineage)
        root, base = self.lineage()
        out._root = root
        out._roff = base + min(start, len(self))  # Arrow clamps the same way
        return out

    def lineage(self):
        """(root, offset): the unsliced column this Series views zero-copy
        and the row of it the view starts at; (self, 0) for a column that is
        no view. Only `slice` of an Arrow-backed column makes a view: take,
        filter, cast and computed expressions make new data. Process-local,
        dropped on pickle. device/residency.py keys slots on it and `concat`
        glues contiguous views back to their root."""
        root = getattr(self, "_root", None)
        if root is None:
            return self, 0
        return root, self._roff

    def head(self, n: int) -> "Series":
        return self.slice(0, min(n, len(self)))

    def take(self, indices) -> "Series":
        idx = _as_index_array(indices)
        if self._pyobjs is not None:
            objs = self._pyobjs
            out = [None if i is None else objs[i] for i in idx.to_pylist()]
            return Series(self._name, self._dtype, None, out)
        return Series(self._name, self._dtype, _combine(self._arrow.take(idx)))

    def filter(self, mask: "Series") -> "Series":
        m = mask._arrow if isinstance(mask, Series) else pa.array(mask, type=pa.bool_())
        if self._pyobjs is not None:
            keep = np.asarray(pc.fill_null(m, False).to_numpy(zero_copy_only=False), dtype=bool)
            return Series(self._name, self._dtype, None, [v for v, k in zip(self._pyobjs, keep) if k])
        return Series(self._name, self._dtype, _combine(self._arrow.filter(m, null_selection_behavior="drop")))

    @classmethod
    def concat(cls, series_list: List["Series"]) -> "Series":
        if not series_list:
            raise ValueError("need at least one series to concat")
        first = series_list[0]
        if any(s._dtype != first._dtype for s in series_list):
            dts = {s._dtype.kind for s in series_list}
            raise ValueError(f"cannot concat series of differing dtypes: {dts}")
        if first._pyobjs is not None:
            objs: list = []
            for s in series_list:
                objs.extend(s._pyobjs)
            return cls(first._name, first._dtype, None, objs)
        # views of one root that tile a contiguous range in order glue back to
        # that range without a copy: the root itself when they cover it, with
        # its dictionary codes and residency slots
        root, start = first.lineage()
        pos = start
        for s in series_list:
            r, off = s.lineage()
            if r is not root or off != pos:
                break
            pos += len(s)
        else:
            whole = start == 0 and pos == len(root)
            return root if whole else root.slice(start, pos)
        return cls(first._name, first._dtype, _combine(pa.concat_arrays([s._arrow for s in series_list])))

    # ---- casts --------------------------------------------------------------------
    def cast(self, dtype: DataType) -> "Series":
        if dtype == self._dtype:
            return self
        if dtype.is_python():
            return Series(self._name, dtype, None, self.to_pylist())
        if self._pyobjs is not None:
            return Series.from_pylist(self._pyobjs, self._name, dtype)
        if self._dtype.is_string() and dtype.is_numeric():
            arr = self._arrow.cast(dtype.to_arrow())
            return Series(self._name, dtype, arr)
        arr = self._arrow.cast(dtype.to_arrow())
        return Series(self._name, dtype, arr)

    # ---- null handling ------------------------------------------------------------
    def is_null(self) -> "Series":
        if self._pyobjs is not None:
            return Series.from_pylist([v is None for v in self._pyobjs], self._name, DataType.bool())
        return Series(self._name, DataType.bool(), pc.is_null(self._arrow))

    def not_null(self) -> "Series":
        if self._pyobjs is not None:
            return Series.from_pylist([v is not None for v in self._pyobjs], self._name, DataType.bool())
        return Series(self._name, DataType.bool(), pc.is_valid(self._arrow))

    def fill_null(self, value: "Series") -> "Series":
        self._require_arrow("fill_null")
        fill = value._arrow
        if len(fill) == 1:
            fill = fill[0]
        return Series(self._name, self._dtype, _combine(pc.fill_null(self._arrow, fill)))

    def drop_nulls(self) -> "Series":
        self._require_arrow("drop_nulls")
        return Series(self._name, self._dtype, _combine(self._arrow.drop_null()))

    # ---- sorting / hashing --------------------------------------------------------
    def argsort(self, descending: bool = False, nulls_first: Optional[bool] = None) -> "Series":
        self._require_arrow("argsort")
        order = "descending" if descending else "ascending"
        if nulls_first is None:
            nulls_first = descending
        placement = "at_start" if nulls_first else "at_end"
        idx = pc.array_sort_indices(self._arrow, order=order, null_placement=placement)
        return Series(self._name, DataType.uint64(), idx.cast(pa.uint64()))

    def sort(self, descending: bool = False, nulls_first: Optional[bool] = None) -> "Series":
        return self.take(self.argsort(descending, nulls_first))

    def hash(self, seed: Optional["Series"] = None) -> "Series":
        """Deterministic 64-bit hash per row (nulls hash to a fixed value).

        Reference parity: src/daft-core/src/array/ops/hash.rs. Host implementation
        vectorizes over numpy; see daft_tpu/core/kernels/hashing.py.
        """
        from .kernels.hashing import hash_series

        return hash_series(self, seed)

    # ---- elementwise arithmetic ---------------------------------------------------
    def _require_arrow(self, op: str) -> pa.Array:
        if self._pyobjs is not None:
            raise ValueError(
                f"operation {op!r} is not supported on Python-object series {self._name!r}; "
                f"cast to a concrete dtype or use a UDF"
            )
        return self._arrow

    def _binary(self, other: "Series", fn, out_dtype: Optional[DataType] = None, scalar_ok: bool = True) -> "Series":
        a = self._require_arrow("binary op")
        b = other._require_arrow("binary op")
        la, lb = len(a), len(b)
        if la != lb:
            # broadcast the length-1 side as an O(1) pyarrow scalar where the kernel
            # allows it, avoiding a full N-row materialization
            if la == 1:
                a = a[0] if scalar_ok else _repeat_array(a, lb)
            elif lb == 1:
                b = b[0] if scalar_ok else _repeat_array(b, la)
            else:
                raise ValueError(f"length mismatch in binary op: {la} vs {lb}")
        out = fn(a, b)
        if isinstance(out, pa.ChunkedArray):
            out = _combine(out)
        dt = out_dtype or DataType.from_arrow(out.type)
        return Series(self._name, dt, out)

    def __add__(self, other: "Series") -> "Series":
        if self._dtype.is_string():
            return self._binary(
                other,
                lambda a, b: pc.binary_join_element_wise(a, b, pa.scalar("", type=pa.large_string())),
            )
        return self._binary(other, pc.add)

    def __sub__(self, other: "Series") -> "Series":
        return self._binary(other, pc.subtract)

    def __mul__(self, other: "Series") -> "Series":
        return self._binary(other, pc.multiply)

    def __truediv__(self, other: "Series") -> "Series":
        def div(a, b):
            a = a.cast(pa.float64()) if not pa.types.is_floating(a.type) else a
            b = b.cast(pa.float64()) if not pa.types.is_floating(b.type) else b
            b = _null_out_zeros(b)
            return pc.divide(a, b)

        return self._binary(other, div)

    def __floordiv__(self, other: "Series") -> "Series":
        out_int = self._dtype.is_integer() and other._dtype.is_integer()

        def fdiv(a, b):
            b_safe = _null_out_zeros(b)
            q = pc.floor(pc.divide(a.cast(pa.float64()), b_safe.cast(pa.float64())))
            if out_int:
                return q.cast(_common_int_type(self._dtype.to_arrow(), other._dtype.to_arrow()) or pa.int64())
            return q

        return self._binary(other, fdiv)

    def __mod__(self, other: "Series") -> "Series":
        def mod(a, b):
            an = _np_values(a)
            bn = _np_values(b)
            res_dtype = np.result_type(an, bn)
            an, bn = np.broadcast_arrays(np.asarray(an), np.asarray(bn))
            with np.errstate(divide="ignore", invalid="ignore"):
                out = np.mod(an, bn, where=(bn != 0), out=np.zeros(an.shape, dtype=res_dtype))
            res = pa.array(out)
            valid = pc.and_(_pa_validity(a, len(res)), _pa_validity(b, len(res)))
            valid = pc.and_(valid, pa.array(bn != 0))
            return pc.if_else(valid, res, pa.nulls(len(res), type=res.type))

        return self._binary(other, mod)

    def __pow__(self, other: "Series") -> "Series":
        return self._binary(other, lambda a, b: pc.power(a.cast(pa.float64()), b.cast(pa.float64())))

    def __neg__(self) -> "Series":
        return Series(self._name, self._dtype, _combine(pc.negate(self._require_arrow("negate"))))

    def abs(self) -> "Series":
        return Series(self._name, self._dtype, _combine(pc.abs(self._require_arrow("abs"))))

    # ---- comparisons --------------------------------------------------------------
    def _cmp(self, other: "Series", fn) -> "Series":
        return self._binary(other, fn, DataType.bool())

    def __eq__(self, other):  # type: ignore[override]
        if isinstance(other, Series):
            fast = self._string_literal_cmp(other, negate=False)
            if fast is not None:
                return fast
            return self._cmp(other, pc.equal)
        return NotImplemented

    def __ne__(self, other):  # type: ignore[override]
        if isinstance(other, Series):
            fast = self._string_literal_cmp(other, negate=True)
            if fast is not None:
                return fast
            return self._cmp(other, pc.not_equal)
        return NotImplemented

    def _filter_codes(self):
        """Dictionary codes for predicate evaluation on low-cardinality string
        columns: integer code compares beat arrow string compares ~5x on wide
        scans. Gated by a head sample; the (one-time, cached) factorize is
        shared with the device grouped-agg dictionary path."""
        if (self._pyobjs is not None or not self._dtype.is_string()
                or len(self) < 65_536):
            return None
        cache = getattr(self, "_device_cache", None)
        if cache is not None and ("dict_reject",) in cache:
            return None
        cached = getattr(self, "_dict_codes", None)
        if cached is None:
            # strided sample (head samples are biased on clustered data)
            step = max(len(self) // 2048, 1)
            import numpy as np

            sampled = self.take(np.arange(0, len(self), step, dtype=np.int64)[:2048])
            if len(set(sampled.to_pylist())) > 256:  # not low-cardinality
                if cache is None:
                    cache = {}
                    object.__setattr__(self, "_device_cache", cache)
                cache[("dict_reject",)] = True
                return None
            cached = self.dict_codes()
        if cached[2] > 4096:
            return None  # vocabulary too large for linear literal lookups
        return cached

    def _string_literal_cmp(self, other: "Series", negate: bool):
        """eq/neq against a 1-row string literal via cached dictionary codes
        (None = take the generic arrow path). Null rows stay null."""
        if len(other) != 1 or not other._dtype.is_string() or other._pyobjs is not None:
            return None
        enc = self._filter_codes()
        if enc is None:
            return None
        codes, values, _k = enc
        target = other.to_pylist()[0]
        if target is None:
            return Series.full_null(self._name, DataType.bool(), len(self))
        try:
            code = values.index(target)
        except ValueError:
            code = -1
        mask = (codes != code) if negate else (codes == code)
        valid = self.validity_numpy()
        arr = pa.array(mask, type=pa.bool_(), mask=~valid) if not valid.all() \
            else pa.array(mask, type=pa.bool_())
        return Series(self._name, DataType.bool(), _combine(arr))

    def __lt__(self, other: "Series") -> "Series":
        return self._cmp(other, pc.less)

    def __le__(self, other: "Series") -> "Series":
        return self._cmp(other, pc.less_equal)

    def __gt__(self, other: "Series") -> "Series":
        return self._cmp(other, pc.greater)

    def __ge__(self, other: "Series") -> "Series":
        return self._cmp(other, pc.greater_equal)

    def eq_null_safe(self, other: "Series") -> "Series":
        def f(a, b):
            eq = pc.equal(a, b)
            both_null = pc.and_(pc.is_null(a), pc.is_null(b))
            return pc.if_else(pc.is_null(eq), both_null, eq)

        return self._binary(other, f, DataType.bool())

    # ---- boolean logic (Kleene) ---------------------------------------------------
    def __and__(self, other: "Series") -> "Series":
        return self._binary(other, pc.and_kleene, DataType.bool())

    def __or__(self, other: "Series") -> "Series":
        return self._binary(other, pc.or_kleene, DataType.bool())

    def __xor__(self, other: "Series") -> "Series":
        return self._binary(other, pc.xor, DataType.bool())

    def __invert__(self) -> "Series":
        return Series(self._name, DataType.bool(), _combine(pc.invert(self._require_arrow("invert"))))

    # ---- misc elementwise ---------------------------------------------------------
    def is_in(self, values: "Series") -> "Series":
        if (values._dtype.is_string() and values._pyobjs is None
                and len(values) <= 64 and values.null_count() == 0):
            # (a null in the value set makes null rows match under arrow
            # semantics — the generic path below handles that case)
            enc = self._filter_codes()
            if enc is not None:
                codes, vocab, k = enc
                targets = set(values.to_pylist())
                # dense codes -> O(n) lookup table beats np.isin's sort path
                lut = np.zeros(max(k, 1), dtype=bool)
                for i, v in enumerate(vocab):
                    if v is not None and v in targets:
                        lut[i] = True
                mask = lut[codes] & self.validity_numpy()
                return Series(self._name, DataType.bool(),
                              _combine(pa.array(mask, type=pa.bool_())))
        self._require_arrow("is_in")
        out = pc.is_in(self._arrow, value_set=values._arrow)
        out = pc.fill_null(out, False)
        return Series(self._name, DataType.bool(), _combine(out))

    def between(self, lower: "Series", upper: "Series") -> "Series":
        ge = self >= lower
        le = self <= upper
        return ge & le

    @staticmethod
    def if_else(predicate: "Series", if_true: "Series", if_false: "Series") -> "Series":
        n = max(len(predicate), len(if_true), len(if_false))

        def bcast(a: pa.Array):
            if len(a) == 1 and n != 1:
                # arrow kernels broadcast scalars natively — no O(n) materialize
                return a[0]
            return a

        t, f = bcast(if_true._arrow), bcast(if_false._arrow)
        p = bcast(predicate._arrow)
        if t.type != f.type:
            target = _common_arrow_type(t.type, f.type)
            t, f = t.cast(target), f.cast(target)
        # n = max(lengths), so at least one operand is always a length-n array
        out = pc.if_else(p, t, f)
        return Series(if_true._name, DataType.from_arrow(out.type), _combine(out))

    # ---- aggregations -------------------------------------------------------------
    def _scalar(self, value, dtype: DataType) -> "Series":
        return Series.from_pylist([value], self._name, dtype)

    def sum(self) -> "Series":
        self._require_arrow("sum")
        if self._dtype.is_null():
            return Series.full_null(self._name, DataType.int64(), 1)
        out_dt = _agg_sum_dtype(self._dtype)
        v = pc.sum(self._arrow).as_py()
        return self._scalar(v, out_dt)

    def product(self) -> "Series":
        """Product of valid values; null when no valid values (reference:
        Expression.product)."""
        self._require_arrow("product")
        out_dt = _agg_sum_dtype(self._dtype)
        valid = self.validity_numpy()
        if not valid.any():
            return Series.full_null(self._name, out_dt, 1)
        vals = self.to_numpy()[valid]
        if out_dt.is_floating():
            v = float(np.prod(vals.astype(np.float64)))
        else:
            v = int(np.prod(vals.astype(np.int64)))
        return self._scalar(v, out_dt)

    def string_agg(self, delimiter: str = "") -> "Series":
        """Join valid string values with the delimiter (reference:
        Expression.string_agg)."""
        vals = [v for v in self.to_pylist() if v is not None]
        return self._scalar(delimiter.join(vals) if vals else None, DataType.string())

    def with_validity(self, valid: np.ndarray) -> "Series":
        """Replace the validity mask (rows where valid is False become null)."""
        if self._pyobjs is not None:
            return Series(self._name, self._dtype, None,
                          [v if k else None for v, k in zip(self._pyobjs, valid)])
        arr = self._arrow
        out = pc.if_else(pa.array(np.asarray(valid, dtype=bool)), arr,
                         pa.nulls(len(self), arr.type if not isinstance(arr, pa.ChunkedArray) else arr.type))
        return Series(self._name, self._dtype, _combine(out))

    def mean(self) -> "Series":
        self._require_arrow("mean")
        v = pc.mean(self._arrow).as_py() if len(self._arrow) else None
        return self._scalar(v, DataType.float64())

    def min(self) -> "Series":
        self._require_arrow("min")
        v = pc.min(self._arrow).as_py() if len(self._arrow) else None
        return self._scalar(v, self._dtype)

    def max(self) -> "Series":
        self._require_arrow("max")
        v = pc.max(self._arrow).as_py() if len(self._arrow) else None
        return self._scalar(v, self._dtype)

    def count(self, mode: str = "valid") -> "Series":
        if self._pyobjs is not None:
            n = len(self._pyobjs)
            nv = self.null_count()
            v = {"valid": n - nv, "null": nv, "all": n}[mode]
        else:
            pc_mode = {"valid": "only_valid", "null": "only_null", "all": "all"}[mode]
            v = pc.count(self._arrow, mode=pc_mode).as_py()
        return self._scalar(v, DataType.uint64())

    def count_distinct(self) -> "Series":
        self._require_arrow("count_distinct")
        v = pc.count_distinct(self._arrow, mode="only_valid").as_py()
        return self._scalar(v, DataType.uint64())

    def any_value(self, ignore_nulls: bool = False) -> "Series":
        arr = self._arrow.drop_null() if ignore_nulls else self._arrow
        v = arr[0].as_py() if len(arr) else None
        return self._scalar(v, self._dtype)

    def stddev(self, ddof: int = 0) -> "Series":
        self._require_arrow("stddev")
        v = pc.stddev(self._arrow, ddof=ddof).as_py() if len(self._arrow) else None
        return self._scalar(v, DataType.float64())

    def var(self, ddof: int = 0) -> "Series":
        self._require_arrow("var")
        v = pc.variance(self._arrow, ddof=ddof).as_py() if len(self._arrow) else None
        return self._scalar(v, DataType.float64())

    def skew(self) -> "Series":
        x = self.to_numpy().astype(np.float64)
        valid = self.validity_numpy()
        x = x[valid]
        if len(x) == 0:
            return self._scalar(None, DataType.float64())
        m = x.mean()
        s2 = ((x - m) ** 2).mean()
        if s2 == 0:
            return self._scalar(0.0, DataType.float64())
        m3 = ((x - m) ** 3).mean()
        return self._scalar(float(m3 / s2**1.5), DataType.float64())

    def bool_and(self) -> "Series":
        self._require_arrow("bool_and")
        v = pc.all(self._arrow, min_count=0).as_py() if len(self._arrow) else None
        if self._arrow.null_count == len(self._arrow) and len(self._arrow) > 0:
            v = None
        return self._scalar(v, DataType.bool())

    def bool_or(self) -> "Series":
        self._require_arrow("bool_or")
        v = pc.any(self._arrow, min_count=0).as_py() if len(self._arrow) else None
        if self._arrow.null_count == len(self._arrow) and len(self._arrow) > 0:
            v = None
        return self._scalar(v, DataType.bool())

    def agg_list(self) -> "Series":
        return Series.from_pylist([self.to_pylist()], self._name, DataType.list(self._dtype))

    def agg_concat(self) -> "Series":
        if not self._dtype.is_list():
            raise ValueError(f"agg_concat requires a list dtype, got {self._dtype}")
        out: list = []
        for v in self.to_pylist():
            if v is not None:
                out.extend(v)
        return Series.from_pylist([out], self._name, self._dtype)

    def agg_set(self) -> "Series":
        """Distinct values as one list, first-occurrence order, nulls dropped
        (reference: daft agg_set / list_agg_distinct semantics)."""
        seen = set()
        out: list = []
        for v in self.to_pylist():
            if v is None:
                continue
            k = v if not isinstance(v, (list, dict)) else repr(v)
            if k not in seen:
                seen.add(k)
                out.append(v)
        return Series.from_pylist([out], self._name, DataType.list(self._dtype))

    def approx_count_distinct(self) -> "Series":
        from .kernels.sketches import hll_count_distinct

        return self._scalar(hll_count_distinct(self), DataType.uint64())

    def approx_percentile(self, percentiles, alpha: float = 0.01) -> "Series":
        """DDSketch approximate percentile(s): scalar float64 for one
        percentile, fixed-size list for several (reference: daft-sketch)."""
        from .kernels.sketches import ddsketch_percentiles

        ps = [percentiles] if isinstance(percentiles, (int, float)) else list(percentiles)
        out = ddsketch_percentiles(self, ps, alpha)
        if isinstance(percentiles, (int, float)):
            return self._scalar(out[0], DataType.float64())
        return Series.from_pylist([out], self._name, DataType.list(DataType.float64()))


# ---- helpers ---------------------------------------------------------------------


# Bytes of a column that one blake2b hashes (`Series._hash_content`): part of
# the fingerprint's definition, so the same in every process.
_HASH_CHUNK_BYTES = 4 << 20


def _chunk_digest(piece) -> bytes:
    return hashlib.blake2b(piece, digest_size=16).digest()


def _byte_view(buf) -> memoryview:
    """`buf` (an Arrow buffer, a memoryview, bytes or a numpy array of any
    fixed dtype) as a flat view of its bytes, copying only what is not
    contiguous."""
    if isinstance(buf, np.ndarray):
        buf = np.ascontiguousarray(buf).reshape(-1).view(np.uint8)
    return memoryview(buf).cast("B")


def note_upload(transfers: int, planes: int) -> None:
    """Count an upload on the h2d path: the calls that moved host planes to
    the device and the planes they carried (planes over transfers says how
    many travel together: 1 where a plane is put by itself)."""
    from ..observability.metrics import registry

    reg = registry()
    reg.inc("h2d_transfers", transfers)
    reg.inc("h2d_planes", planes)


def _repeat_array(a: pa.Array, n: int) -> pa.Array:
    if n == 0:
        return a.slice(0, 0)
    return _combine(pa.repeat(a[0], n))


def _null_out_zeros(b):
    """Replace zeros with null (divide-by-zero -> null); works for Array or Scalar."""
    if isinstance(b, pa.Scalar):
        if not b.is_valid or b.as_py() == 0:
            return pa.scalar(None, type=b.type)
        return b
    return pc.if_else(pc.equal(b, _zero_like(b.type)), pa.nulls(len(b), type=b.type), b)


def _np_values(x) -> np.ndarray:
    """Dense numpy values of an arrow Array or Scalar (nulls -> 0)."""
    if isinstance(x, pa.Scalar):
        v = x.as_py()
        return np.asarray(0 if v is None else v)
    from ..datatype import DataType as _DT

    return Series("tmp", _DT.from_arrow(x.type), x).to_numpy()


def _pa_validity(x, n: int) -> pa.Array:
    if isinstance(x, pa.Scalar):
        return pa.array(np.full(n, x.is_valid))
    return pc.is_valid(x)


def _null_fill_scalar(t: pa.DataType, fill):
    if pa.types.is_floating(t):
        return pa.scalar(float("nan"), type=t)
    if pa.types.is_date32(t):
        return pa.scalar(0, type=pa.int32()).cast(t)
    if pa.types.is_temporal(t):
        return pa.scalar(0, type=pa.int64()).cast(t)
    return pa.scalar(fill, type=t)


def _zero_like(t: pa.DataType):
    if pa.types.is_floating(t):
        return pa.scalar(0.0, type=t)
    return pa.scalar(0, type=t)


def _common_int_type(a: pa.DataType, b: pa.DataType):
    if pa.types.is_integer(a) and pa.types.is_integer(b):
        na, nb = np.dtype(a.to_pandas_dtype()), np.dtype(b.to_pandas_dtype())
        return pa.from_numpy_dtype(np.promote_types(na, nb))
    return None


def _common_arrow_type(a: pa.DataType, b: pa.DataType) -> pa.DataType:
    if a == b:
        return a
    if pa.types.is_null(a):
        return b
    if pa.types.is_null(b):
        return a
    try:
        na, nb = np.dtype(a.to_pandas_dtype()), np.dtype(b.to_pandas_dtype())
        return pa.from_numpy_dtype(np.promote_types(na, nb))
    except Exception:
        raise ValueError(f"no common type for {a} and {b}")


def _agg_sum_dtype(dt: DataType) -> DataType:
    if dt.is_signed_integer():
        return DataType.int64()
    if dt.is_unsigned_integer():
        return DataType.uint64()
    if dt.is_floating():
        return dt if dt.kind == "float32" else DataType.float64()
    if dt.is_decimal():
        return dt
    if dt.is_boolean():
        return DataType.uint64()
    if dt.is_null():
        # a column with no typed values (empty / all-null input) sums to null
        return DataType.null()
    raise ValueError(f"cannot sum dtype {dt}")


def _as_index_array(indices) -> pa.Array:
    if isinstance(indices, Series):
        return indices.to_arrow()
    if isinstance(indices, np.ndarray):
        return pa.array(indices)
    return pa.array(indices, type=pa.int64())
