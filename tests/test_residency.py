"""HBM residency manager (daft_tpu/device/residency.py): budget-bounded LRU
eviction, one-slot reuse for varying predicate literals, pin-during-execution
safety, cache hits with zero re-transfer, and the zero-overhead host-path
guard. Device paths run with device_mode="on" on the CPU backend (jit
semantics identical to TPU)."""

import time

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col, lit
from daft_tpu.config import execution_config_ctx
from daft_tpu.device.residency import identity_token, manager
from daft_tpu.observability.metrics import registry
from daft_tpu.ops import counters


@pytest.fixture(scope="module")
def star():
    rng = np.random.default_rng(17)
    n = 8_192
    fact = daft_tpu.from_pydict({
        "f_k": [int(x) for x in rng.integers(0, 400, n)],
        "f_v": rng.uniform(0, 100, n).tolist(),
        "f_q": rng.integers(1, 50, n).tolist(),
    }).collect()
    dim = daft_tpu.from_pydict({
        "d_k": list(range(400)),
        "d_grp": [f"g{i % 6}" for i in range(400)],
        "d_w": [float(i % 17) for i in range(400)],
    }).collect()
    return fact, dim


def _query(fact, dim, threshold: float):
    return (fact.join(dim, left_on="f_k", right_on="d_k")
            .where(col("d_w") < lit(threshold))
            .groupby("d_grp")
            .agg(col("f_v").sum().alias("sv"), col("f_q").sum().alias("sq"))
            .sort("d_grp"))


def _host_result(fact, dim, threshold: float):
    with execution_config_ctx(device_mode="off"):
        return _query(fact, dim, threshold).to_pydict()


def _assert_close(host, dev):
    assert list(host.keys()) == list(dev.keys())
    for c in host:
        assert len(host[c]) == len(dev[c]), c
        for a, b in zip(host[c], dev[c]):
            if isinstance(a, float) and isinstance(b, float):
                assert abs(a - b) <= 1e-6 * max(1.0, abs(a)), (c, a, b)
            else:
                assert a == b, (c, a, b)


def test_budget_bounded_eviction_varying_literals(star):
    """A loop of device-join queries with varying filter literals keeps
    registered device bytes <= budget (evictions observed via counters) and
    returns host-identical results."""
    fact, dim = star
    manager().clear()
    counters.reset()
    budget = 96 * 1024  # well below the query's full working set
    with execution_config_ctx(device_mode="on", hbm_budget_bytes=budget):
        for i in range(6):
            threshold = float(3 + i)
            dev = _query(fact, dim, threshold).to_pydict()
            _assert_close(_host_result(fact, dim, threshold), dev)
            resident = manager().bytes_resident()
            assert resident <= budget, \
                f"iteration {i}: {resident} bytes resident > {budget} budget"
    assert counters.hbm_evictions > 0, "budget never forced an eviction"
    assert registry().get("hbm_eviction_bytes") > 0


def test_varying_literals_reuse_one_slot(star):
    """Literal-dependent caches (visibility planes, packed dim matrices) are
    structure-keyed: re-running the same query shape with a different literal
    must not add entries (the ADVICE r5 unbounded-growth bug)."""
    fact, dim = star
    manager().clear()
    with execution_config_ctx(device_mode="on"):
        _query(fact, dim, 5.0).to_pydict()
        entries_after_first = manager().entry_count()
        _query(fact, dim, 9.0).to_pydict()   # same shape, new literal
        _query(fact, dim, 2.0).to_pydict()
        assert manager().entry_count() == entries_after_first
        # and the varying-literal runs still compute the literal's result
        _assert_close(_host_result(fact, dim, 2.0),
                      _query(fact, dim, 2.0).to_pydict())


def test_cache_hit_second_identical_query(star):
    """The second run of an identical query is served from HBM: residency
    hits, no new uploads (zero h2d delta — the QueryEnd.metrics contract)."""
    fact, dim = star
    manager().clear()
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        first = _query(fact, dim, 7.0).to_pydict()
        h2d_after_first = registry().get("hbm_h2d_bytes")
        hits_after_first = registry().get("hbm_cache_hits")
        assert h2d_after_first > 0  # first run really uploaded
        second = _query(fact, dim, 7.0).to_pydict()
    _assert_close(first, second)
    assert registry().get("hbm_cache_hits") > hits_after_first
    assert registry().get("hbm_h2d_bytes") == h2d_after_first, \
        "second identical query re-uploaded column planes"


def test_pin_during_execution_under_tiny_budget(star):
    """With a budget far below the query's working set, in-flight buffers are
    pinned (never evicted mid-run) and results stay correct; the budget
    re-enforces after the query ends."""
    fact, dim = star
    manager().clear()
    counters.reset()
    budget = 4 * 1024
    with execution_config_ctx(device_mode="on", hbm_budget_bytes=budget):
        dev = _query(fact, dim, 8.0).to_pydict()
        _assert_close(_host_result(fact, dim, 8.0), dev)
        # post-query: everything unpinned, budget enforced again
        assert manager().bytes_resident() <= budget
    assert registry().get("hbm_pins") > 0, "no entry was pinned during the run"


def test_zero_overhead_when_no_device_used(star):
    """A host-only query never touches the manager: no entries, no counters."""
    fact, dim = star
    manager().clear()
    counters.reset()
    with execution_config_ctx(device_mode="off"):
        _query(fact, dim, 4.0).to_pydict()
    stats = manager().stats()
    assert stats["hbm_entries"] == 0
    assert stats["hbm_bytes_resident"] == 0
    assert registry().get("hbm_cache_misses") == 0
    assert registry().get("hbm_h2d_bytes") == 0


def test_budget_env_and_gauges(star):
    """The gauges land in the metrics registry snapshot (the path QueryEnd /
    explain_analyze / bench read), and high-water >= resident."""
    fact, dim = star
    manager().clear()
    with execution_config_ctx(device_mode="on"):
        _query(fact, dim, 6.0).to_pydict()
    snap = registry().snapshot()
    assert snap.get("hbm_bytes_resident", 0) > 0
    assert snap.get("hbm_bytes_high_water", 0) >= snap["hbm_bytes_resident"]
    assert manager().stats()["hbm_bytes_resident"] == snap["hbm_bytes_resident"]


def test_entries_die_with_their_series():
    """Entries anchored on a collected table are released when the table's
    Series die (no leak of device buffers past their host owner)."""
    manager().clear()
    fact = daft_tpu.from_pydict({
        "k": list(range(2048)), "v": [float(i) for i in range(2048)],
    }).collect()
    with execution_config_ctx(device_mode="on"):
        fact.agg(col("v").sum().alias("s")).to_pydict()
    assert manager().entry_count() > 0
    del fact
    import gc

    gc.collect()
    assert manager().entry_count() == 0


def test_identity_token_monotonic_and_sticky():
    a = daft_tpu.from_pydict({"x": [1]}).collect()
    b = daft_tpu.from_pydict({"x": [2]}).collect()
    ta1, ta2 = identity_token(a), identity_token(a)
    tb = identity_token(b)
    assert ta1 == ta2
    assert ta1 != tb


def test_identity_token_not_pickled():
    """Tokens are process-local: shipping one to a worker would collide with
    the receiver's independently-counted tokens and alias distinct objects
    in advisory caches (the id()-reuse bug class, cross-process edition)."""
    import pickle

    from daft_tpu.core.micropartition import MicroPartition
    from daft_tpu.core.series import Series

    mp = MicroPartition.from_pydict({"x": [1, 2]})
    identity_token(mp)
    assert getattr(pickle.loads(pickle.dumps(mp)), "_rtoken", None) is None
    s = Series.from_pylist([1, 2], "s")
    identity_token(s)
    assert getattr(pickle.loads(pickle.dumps(s)), "_rtoken", None) is None


def test_cost_weighted_eviction_cheapest_first():
    """Under budget pressure, the cheaper-to-rebuild entry in the oldest
    recency bucket evicts first: a plain (re-uploadable) plane goes before an
    equally-recent expensive one (join index / dictionary planes carry host
    factorize work via rebuild_rows), and the saved rebuild cost is counted."""
    import jax.numpy as jnp

    m = manager()
    m.clear()
    saved_before = registry().get("hbm_evict_cost_saved")

    class Anchor:  # plain object: identity-keyed, no stable content
        pass

    dear, cheap, extra = Anchor(), Anchor(), Anchor()

    def one_kb():
        # explicit f32: entry size must not depend on whether x64 mode was
        # enabled by earlier tests (jax_setup import order)
        return jnp.ones(256, dtype=jnp.float32)

    with execution_config_ctx(hbm_budget_bytes=2 * 1024 + 512):
        # insert the EXPENSIVE entry first: it is the LRU-oldest, so pure
        # recency eviction would take it — cost weighting must not
        m.get_or_build(dear, ("d",), (), one_kb, rebuild_rows=50_000_000)
        m.get_or_build(cheap, ("c",), (), one_kb)
        m.get_or_build(extra, ("x",), (), one_kb)
        assert m.is_resident(dear, ("d",)), \
            "expensive-to-rebuild plane was evicted despite a cheap candidate"
        assert not m.is_resident(cheap, ("c",))
        assert m.bytes_resident() <= 2 * 1024 + 512
    assert registry().get("hbm_evict_cost_saved") > saved_before
    m.clear()


def test_eviction_keeps_recency_with_few_entries():
    """Cost weighting must not invert recency wholesale: with only a cold
    expensive entry and a hot cheap one, the eviction bucket is the oldest
    HALF (= the cold entry alone), so the squatter leaves and the hot plane
    stays — not the thrash of re-uploading the hot plane every query."""
    import jax.numpy as jnp

    m = manager()
    m.clear()

    class Anchor:
        pass

    cold_dear, hot_cheap = Anchor(), Anchor()

    def one_kb():
        return jnp.ones(256, dtype=jnp.float32)

    with execution_config_ctx(hbm_budget_bytes=1024 + 512):
        m.get_or_build(cold_dear, ("d",), (), one_kb, rebuild_rows=50_000_000)
        m.get_or_build(hot_cheap, ("c",), (), one_kb)  # over budget now
        assert m.is_resident(hot_cheap, ("c",))
        assert not m.is_resident(cold_dear, ("d",)), \
            "rebuild cost protected a cold squatter over the hot plane"
    m.clear()


def test_eviction_bucket_ignores_pinned_padding():
    """Pinned entries must not widen the recency window: with one pinned
    entry plus a cold expensive and a hot cheap plane, the oldest-half bucket
    spans the UNPINNED entries only (= the cold one), so the hot plane
    survives."""
    import jax.numpy as jnp

    m = manager()
    m.clear()

    class Anchor:
        pass

    pinned, cold_dear, hot_cheap = Anchor(), Anchor(), Anchor()

    def one_kb():
        return jnp.ones(256, dtype=jnp.float32)

    with execution_config_ctx(hbm_budget_bytes=2 * 1024 + 512):
        # both registered (and released) under budget first
        with m.pin_scope():
            m.get_or_build(pinned, ("pin",), (), one_kb)
            m.get_or_build(cold_dear, ("d",), (), one_kb,
                           rebuild_rows=50_000_000)
        with m.pin_scope():
            # re-pin one entry (moves to MRU), then push over budget: LRU
            # order is [cold_dear, pinned, hot_cheap] with only cold_dear and
            # hot_cheap unpinned — the half-window must span those two, not
            # all three, so the single candidate is cold_dear
            m.get_or_build(pinned, ("pin",), (), one_kb)
            m.get_or_build(hot_cheap, ("c",), (), one_kb)
            assert m.is_resident(hot_cheap, ("c",)), \
                "pinned padding widened the bucket onto the hot plane"
            assert not m.is_resident(cold_dear, ("d",))
    m.clear()


def test_cost_weighted_eviction_never_touches_pins():
    """Pinned entries stay resident whatever their rebuild cost: a pinned
    cheap plane survives while unpinned entries (even expensive ones) evict."""
    import jax.numpy as jnp

    m = manager()
    m.clear()

    class Anchor:
        pass

    pinned_cheap, dear = Anchor(), Anchor()

    def one_kb():
        return jnp.ones(256, dtype=jnp.float32)

    with execution_config_ctx(hbm_budget_bytes=1024 + 512):
        # expensive entry registered OUTSIDE any pin scope: evictable
        m.get_or_build(dear, ("d",), (), one_kb, rebuild_rows=10_000_000)
        with m.pin_scope():
            # pushes over budget; the only unpinned candidate is `dear`,
            # whose high rebuild cost must not protect it from a pin
            m.get_or_build(pinned_cheap, ("p",), (), one_kb)
            assert m.is_resident(pinned_cheap, ("p",))
            assert not m.is_resident(dear, ("d",)), \
                "unpinned entry should have evicted, not the pinned one"
    m.clear()


def test_stable_rebind_serves_unpickled_copy_without_reupload():
    """A content-identical Series (e.g. a worker's freshly-unpickled repeat
    sub-plan input) rebinds the existing slot: one entry, no new h2d bytes,
    and the digest advertises the slot under the same stable key both
    times."""
    import pickle

    from daft_tpu.core.series import Series
    from daft_tpu.device.residency import stable_slot_key

    m = manager()
    m.clear()
    s = Series.from_pylist(list(range(4096)), "c")
    s.to_device_cached(4096, f32=True)
    h2d = registry().get("hbm_h2d_bytes")
    rehits = registry().get("hbm_stable_rehits")
    digest1 = dict(m.digest())
    assert stable_slot_key(s, ("col", 4096, True)) in digest1

    s2 = pickle.loads(pickle.dumps(s))
    assert s2 is not s and getattr(s2, "_rtoken", None) is None
    s2.to_device_cached(4096, f32=True)
    assert registry().get("hbm_h2d_bytes") == h2d, "rebind re-uploaded"
    assert registry().get("hbm_stable_rehits") == rehits + 1
    assert m.entry_count() == 1
    assert dict(m.digest()) == digest1
    m.clear()


def test_orphan_retention_is_opt_in(monkeypatch):
    """Driver default (DAFT_TPU_HBM_ORPHANS unset): entries still die with
    their anchor. With a positive cap (the worker-pool environment), a stable
    entry survives its anchor and a content-equal anchor rebinds it."""
    import gc
    import pickle

    from daft_tpu.core.series import Series

    m = manager()
    m.clear()
    blob = pickle.dumps(Series.from_pylist(list(range(512)), "c"))

    s = pickle.loads(blob)
    s.to_device_cached(512, f32=True)
    del s
    gc.collect()
    assert m.entry_count() == 0  # strict anchor-coupled lifetime by default

    monkeypatch.setenv("DAFT_TPU_HBM_ORPHANS", "8")
    m.clear()  # re-reads the cap
    s = pickle.loads(blob)
    s.to_device_cached(512, f32=True)
    h2d = registry().get("hbm_h2d_bytes")
    del s
    gc.collect()
    assert m.entry_count() == 1  # orphaned but retained (content-addressed)
    s2 = pickle.loads(blob)
    s2.to_device_cached(512, f32=True)  # rebinds the orphan
    assert registry().get("hbm_h2d_bytes") == h2d
    assert m.entry_count() == 1
    m.clear()


def test_rebuild_in_place_keeps_pin():
    """A dep/literal mismatch inside a pin scope rebuilds the slot in place;
    the replacement must inherit the pin so a tight budget cannot evict a
    plane the executing query is about to read."""
    import jax.numpy as jnp

    from daft_tpu.core.series import Series

    m = manager()
    m.clear()
    anchor = Series.from_pylist(list(range(8)), "anchor")
    d1, d2 = object(), object()
    with execution_config_ctx(hbm_budget_bytes=1):  # below any entry's size
        with m.pin_scope():
            m.get_or_build(anchor, ("k",), (d1,), lambda: jnp.ones(1024))
            m.get_or_build(anchor, ("k",), (d2,), lambda: jnp.ones(1024))
            # pinned despite the over-budget rebuild: still resident
            assert m.entry_count() == 1
            assert m.bytes_resident() > 1
        # scope closed: the pin released exactly once, budget re-enforces
        assert m.entry_count() == 0
    m.clear()


# ---- slots follow the data: lineage of zero-copy views --------------------------------

def _lineage_root(n=4096):
    from daft_tpu.core.series import Series

    return Series.from_pylist([(i * 7) % 1000 for i in range(n)], "c")


def _plane(s):
    import jax.numpy as jnp

    return jnp.asarray(s.to_numpy())


def _delta(names, fn):
    before = {k: registry().get(k) for k in names}
    fn()
    return {k: registry().get(k) - before[k] for k in names}


_LOOKUP = ("hbm_cache_hits", "hbm_cache_misses", "hbm_lineage_hits", "hbm_stable_rehits")


@pytest.mark.parametrize("again,hits,lineage,stable,misses", [
    # a separately made slice of the same rows: the slot, and only lineage finds it
    (lambda r, o: r.slice(1024, 2048), 1, 1, 0, 0),
    # a slice of a slice resolves to the root
    (lambda r, o: r.slice(512, 3072).slice(512, 1536), 1, 1, 0, 0),
    # other rows of the root: another slot
    (lambda r, o: r.slice(1024, 2049), 0, 0, 0, 1),
    (lambda r, o: r.slice(0, 1024), 0, 0, 0, 1),
    # the same rows copied (new data): not by lineage; the deps-free slot may
    # still rebind through its content key, as before
    (lambda r, o: r.slice(1024, 2048).take(list(range(1024))), 1, 0, 1, 0),
    # another root of equal content, same range: likewise
    (lambda r, o: o.slice(1024, 2048), 1, 0, 1, 0),
], ids=["same_range", "slice_of_slice", "other_length", "other_offset",
        "copied_rows", "other_root_equal_content"])
def test_view_slot_identity(again, hits, lineage, stable, misses):
    m = manager()
    m.clear()
    root, other = _lineage_root(), _lineage_root()
    first = root.slice(1024, 2048)
    m.get_or_build(first, ("plane",), (), lambda: _plane(first))
    second = again(root, other)
    d = _delta(_LOOKUP, lambda: m.get_or_build(
        second, ("plane",), (), lambda: _plane(second)))
    assert d == {"hbm_cache_hits": hits, "hbm_cache_misses": misses,
                 "hbm_lineage_hits": lineage, "hbm_stable_rehits": stable}
    m.clear()


def test_same_object_hit_is_not_a_lineage_hit():
    m = manager()
    m.clear()
    root = _lineage_root()
    view = root.slice(0, 100)
    for anchor in (root, view):
        m.get_or_build(anchor, ("plane",), (), lambda a=anchor: _plane(a))
        d = _delta(_LOOKUP, lambda a=anchor: m.get_or_build(
            a, ("plane",), (), lambda: _plane(a)))
        assert d["hbm_cache_hits"] == 1 and d["hbm_lineage_hits"] == 0
    m.clear()


def test_view_of_all_of_its_root_is_the_root():
    m = manager()
    m.clear()
    root = _lineage_root()
    m.get_or_build(root, ("plane",), (), lambda: _plane(root))
    whole = root.slice(0, len(root))
    assert m.is_resident(whole, ("plane",))
    d = _delta(_LOOKUP, lambda: m.get_or_build(whole, ("plane",), (), lambda: _plane(whole)))
    assert d["hbm_cache_hits"] == 1 and d["hbm_lineage_hits"] == 1
    assert m.entry_count() == 1
    m.clear()


def test_python_object_column_has_no_lineage():
    from daft_tpu import DataType
    from daft_tpu.core.series import Series

    m = manager()
    m.clear()
    root = Series.from_pylist([object() for _ in range(64)], "p", DataType.python())
    a, b = root.slice(8, 16), root.slice(8, 16)
    m.get_or_build(a, ("k",), (), lambda: 1)
    d = _delta(_LOOKUP, lambda: m.get_or_build(b, ("k",), (), lambda: 2))
    assert d["hbm_cache_misses"] == 1 and d["hbm_cache_hits"] == 0
    m.clear()


@pytest.mark.parametrize("dep,hit", [
    (lambda dim, other: dim.slice(0, 256), True),        # the same rows, another object
    (lambda dim, other: dim.slice(0, 255), False),       # other rows
    (lambda dim, other: other.slice(0, 256), False),     # equal content, another root:
    (lambda dim, other: other, False),                   # never served another's index
], ids=["same_rows", "other_rows", "other_root_view", "other_root"])
def test_series_deps_compare_by_lineage(dep, hit):
    m = manager()
    m.clear()
    fact, dim, other = _lineage_root(), _lineage_root(512), _lineage_root(512)
    idx = np.arange(8)                                   # a non-Series dep stays `is`
    m.get_or_build(fact.slice(0, 1024), ("uki",), (dim.slice(0, 256), idx), lambda: "first")
    d = _delta(_LOOKUP, lambda: m.get_or_build(
        fact.slice(0, 1024), ("uki",), (dep(dim, other), idx), lambda: "second"))
    assert (d["hbm_cache_hits"], d["hbm_cache_misses"]) == ((1, 0) if hit else (0, 1))
    assert m.entry_count() == 1, "a mismatch rebuilds in place"
    # an equal but distinct array is not the cached one
    d = _delta(_LOOKUP, lambda: m.get_or_build(
        fact.slice(0, 1024), ("uki",), (dep(dim, other), np.arange(8)), lambda: "third"))
    assert d["hbm_cache_misses"] == 1
    m.clear()


def test_literals_still_compared_for_views():
    m = manager()
    m.clear()
    root = _lineage_root()
    m.get_or_build(root.slice(0, 64), ("vis",), (), lambda: "a", literals=(1,))
    assert m.get_or_build(root.slice(0, 64), ("vis",), (), lambda: "b", literals=(1,)) == "a"
    assert m.get_or_build(root.slice(0, 64), ("vis",), (), lambda: "c", literals=(2,)) == "c"
    assert m.entry_count() == 1
    m.clear()


def test_view_entries_live_with_the_root_not_the_view():
    import gc

    m = manager()
    m.clear()
    root = _lineage_root()
    view = root.slice(100, 200)
    m.get_or_build(view, ("plane",), (), lambda: _plane(view))
    del view
    gc.collect()
    assert m.entry_count() == 1, "a morsel object died: its rows are still resident"
    again = root.slice(100, 200)
    assert _delta(_LOOKUP, lambda: m.get_or_build(
        again, ("plane",), (), lambda: _plane(again)))["hbm_lineage_hits"] == 1
    del again, root
    gc.collect()
    assert m.entry_count() == 0, "the root died: nothing can present these rows again"


def test_series_deps_are_not_kept_alive():
    import gc
    import weakref

    m = manager()
    m.clear()
    fact, dim = _lineage_root(), _lineage_root(128)
    m.get_or_build(fact, ("uki",), (dim,), lambda: "idx")
    ref = weakref.ref(dim)
    del dim
    gc.collect()
    assert ref() is None, "a Series dep is held as a token, never reused"
    m.clear()


def test_pin_scope_and_budget_eviction_hold_for_views():
    import jax.numpy as jnp

    m = manager()
    m.clear()
    root = _lineage_root()
    with execution_config_ctx(hbm_budget_bytes=1):       # below any entry's size
        with m.pin_scope():
            for lo in (0, 1024, 2048):
                m.get_or_build(root.slice(lo, lo + 1024), ("plane",), (),
                               lambda: jnp.ones(1024))
            assert m.entry_count() == 3                  # pinned: over budget, all held
            # a fresh view of pinned rows hits, and does not pin twice
            pins = registry().get("hbm_pins")
            m.get_or_build(root.slice(1024, 2048), ("plane",), (), lambda: jnp.ones(1024))
            assert registry().get("hbm_pins") == pins
        assert m.entry_count() == 0                      # scope closed: budget enforced
    evicted = registry().get("hbm_evictions")
    with execution_config_ctx(hbm_budget_bytes=3 * 1024 * 4):
        for lo in (0, 512, 1024, 1536, 2048):            # distinct content, 4 KiB each
            part = root.slice(lo, lo + 512)
            m.get_or_build(part, ("plane", lo), (), lambda: jnp.ones(1024, dtype=jnp.float32))
        assert m.bytes_resident() <= 3 * 1024 * 4
        assert registry().get("hbm_evictions") > evicted
    m.clear()


def test_unpickled_view_has_no_lineage_and_rebinds_by_content():
    import pickle

    m = manager()
    m.clear()
    root = _lineage_root()
    view = root.slice(1024, 2048)
    view.to_device_cached(1024, f32=True)
    h2d = registry().get("hbm_h2d_bytes")
    copy = pickle.loads(pickle.dumps(view))
    assert copy.lineage() == (copy, 0)
    d = _delta(_LOOKUP, lambda: copy.to_device_cached(1024, f32=True))
    assert d == {"hbm_cache_hits": 1, "hbm_cache_misses": 0,
                 "hbm_lineage_hits": 0, "hbm_stable_rehits": 1}
    assert registry().get("hbm_h2d_bytes") == h2d and m.entry_count() == 1
    m.clear()


def test_hit_does_not_fingerprint_the_column(monkeypatch):
    """The content hash (blake2b over the column's Arrow buffers where they
    lie, in chunks: tests/test_content_fingerprint.py) is for the stable-key
    rebind: computed once the identity probe has missed, never on a hit."""
    from daft_tpu.core.series import Series

    m = manager()
    m.clear()
    root = _lineage_root()
    calls = []
    real = Series.content_fingerprint
    monkeypatch.setattr(Series, "content_fingerprint",
                        lambda self: calls.append(len(self)) or real(self))
    m.get_or_build(root.slice(0, 512), ("plane",), (), lambda: 1)
    assert calls == [512]                                # the miss hashed the morsel once
    for _ in range(3):
        m.get_or_build(root.slice(0, 512), ("plane",), (), lambda: 2)
    assert calls == [512]
    m.clear()


def test_scan_query_has_no_lineage_hits():
    """One unsliced batch a query: every anchor is its own root, every hit is
    under the object the entry was built under."""
    manager().clear()
    fact = daft_tpu.from_pydict({
        "k": [i % 5 for i in range(4096)], "v": [float(i) for i in range(4096)],
    }).collect()
    with execution_config_ctx(device_mode="on"):
        q = lambda: fact.where(col("v") > lit(10.0)).agg(col("v").sum().alias("s")).to_pydict()
        first = q()
        again = []
        d = _delta(_LOOKUP + ("hbm_h2d_bytes",), lambda: again.append(q()))
    assert again == [first]
    assert d["hbm_cache_hits"] > 0 and d["hbm_cache_misses"] == 0
    assert d["hbm_lineage_hits"] == 0 and d["hbm_h2d_bytes"] == 0
    manager().clear()


def test_concurrent_lookups_of_views_keep_the_books():
    """Stage threads and pool morsels look slots up at once. Fresh views of
    eight ranges of one root from more threads than cores: every lookup gets
    its own range's value, one entry a range stays, and the byte count is the
    sum of what the entries hold."""
    import sys
    import threading

    import jax.numpy as jnp

    from daft_tpu.device.residency import device_nbytes

    m = manager()
    m.clear()
    root = _lineage_root(8 * 256)
    dim = _lineage_root(64)
    wrong, stop = [], time.monotonic() + 20.0

    def worker(seed):
        rng = np.random.default_rng(seed)
        for _ in range(150):
            if time.monotonic() > stop:
                wrong.append("timed out")
                return
            lo = int(rng.integers(0, 8)) * 256
            view = root.slice(lo, lo + 256)
            plane = m.get_or_build(view, ("plane",), (), lambda: jnp.full(256, lo))
            idx = m.get_or_build(view, ("uki",), (dim.slice(0, 64),), lambda: ("idx", lo))
            if int(plane[0]) != lo or idx != ("idx", lo):
                wrong.append((lo, int(plane[0]), idx))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(s,)) for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert wrong == []
    assert m.entry_count() == 16                         # 8 ranges x 2 slots
    with m._lock:
        assert m._bytes == sum(device_nbytes(e.value) for e in m._entries.values())
    m.clear()


# ---- a batch's planes asked for at once (PR 35) -----------------------------------------

H2D = ("h2d_transfers", "h2d_planes", "hbm_h2d_bytes", "hbm_cache_misses", "hbm_cache_hits")


def _h2d():
    return {k: registry().get(k) for k in H2D}


def test_a_resident_table_uploads_a_column_at_a_time_and_once():
    """First touch of a table that stays: every column by itself, a transfer a
    plane, its own validity plane among them; the second query uploads nothing."""
    m = manager()
    m.clear()
    n = 3_000                                             # bucket 4,096
    df = daft_tpu.from_pydict({
        "g": ["a", "b", "c"] * (n // 3),
        "v": [float(i) for i in range(n)],
        "w": [float(i % 7) for i in range(n)],
    }).collect()

    def query():
        return df.groupby("g").agg(col("v").sum().alias("s"), col("w").mean().alias("m")).sort("g")

    with execution_config_ctx(device_mode="off"):
        want = query().to_pydict()
    with execution_config_ctx(device_mode="on", device_min_rows=1):
        before = _h2d()
        first = query().to_pydict()
        touched = _h2d()
        second = query().to_pydict()
        after = _h2d()
    assert first == second
    assert first["g"] == want["g"] and first["s"] == pytest.approx(want["s"], rel=1e-6)
    delta = {k: touched[k] - before[k] for k in H2D}
    # v and w: a float32 value plane and a bool validity plane each, then g's code plane
    assert delta["h2d_transfers"] == delta["h2d_planes"] == 2 * 2 + 1
    assert delta["hbm_h2d_bytes"] == 2 * 4096 * (4 + 1)
    assert delta["hbm_cache_misses"] == 3
    again = {k: after[k] - touched[k] for k in H2D}
    assert again["hbm_h2d_bytes"] == again["h2d_transfers"] == again["h2d_planes"] == 0
    assert again["hbm_cache_misses"] == 0 and again["hbm_cache_hits"] == 3
    m.clear()


def test_a_streamed_batchs_planes_are_pinned_in_their_scope_and_go_with_it():
    """batch_planes inside a transient scope: one transfer, the planes slots of
    their own and pinned while the scope is open whatever the budget, evicted
    at its exit; a column without nulls reads the dispatch's row mask."""
    from daft_tpu.core.recordbatch import RecordBatch
    from daft_tpu.ops.stage import batch_planes, device_row_mask

    m = manager()
    m.clear()
    n = 700                                               # bucket 1,024
    batch = RecordBatch.from_pydict({
        "x": [float(i) for i in range(n)],
        "y": [None if i % 5 == 0 else float(i) for i in range(n)],
        "k": ["p", "q"] * (n // 2),
    })
    key = batch.get_column("k")
    codes = key.dict_codes()[0]
    with execution_config_ctx(hbm_budget_bytes=1):        # below any plane's size
        with m.pin_scope(transient=True):
            before = _h2d()
            pins = registry().get("hbm_pins")
            dcols, dcodes = batch_planes(batch, ["x", "y"], 1024, True, key_codes=[(key, codes)])
            delta = {k: _h2d()[k] - before[k] for k in H2D}
            assert delta["h2d_transfers"] == 1
            assert delta["h2d_planes"] == 4               # x, y, y's validity, k's codes
            assert delta["hbm_h2d_bytes"] == 2 * 1024 * 4 + 1024
            assert delta["hbm_cache_misses"] == 3 and registry().get("hbm_pins") == pins + 3
            assert m.entry_count() == 3                   # over budget, pinned, all held
            assert dcols["x"][1] is device_row_mask(n, 1024)
            assert dcols["y"][1] is not dcols["x"][1]
            assert np.asarray(dcols["y"][1]).sum() == n - n // 5
            assert np.asarray(dcodes[0])[:n].tolist() == codes.tolist()
            assert not np.asarray(dcodes[0])[n:].any()
            # asked again in the same scope: found, nothing moves
            again, _ = batch_planes(batch, ["x", "y"], 1024, True, key_codes=[(key, codes)])
            assert again["x"][0] is dcols["x"][0] and again["y"][1] is dcols["y"][1]
            assert _h2d()["h2d_transfers"] == before["h2d_transfers"] + 1
        assert m.entry_count() == 0                       # scope closed: budget enforced
    m.clear()


def test_outside_a_transient_scope_a_batchs_planes_are_built_one_by_one():
    from daft_tpu.core.recordbatch import RecordBatch
    from daft_tpu.ops.stage import batch_planes, device_row_mask

    m = manager()
    m.clear()
    batch = RecordBatch.from_pydict({"x": [1.0, 2.0, 3.0], "y": [4.0, None, 6.0]})
    before = _h2d()
    with m.pin_scope():
        dcols, dcodes = batch_planes(batch, ["x", "y"], 512, True)
    delta = {k: _h2d()[k] - before[k] for k in H2D}
    assert dcodes == []
    assert delta["h2d_transfers"] == delta["h2d_planes"] == 4
    assert delta["hbm_h2d_bytes"] == 2 * 512 * (4 + 1)
    assert dcols["x"][1] is not device_row_mask(3, 512)   # a validity plane of the column's own
    assert np.asarray(dcols["x"][1]).tolist() == [True] * 3 + [False] * 509
    assert dcols["x"] is batch.get_column("x").to_device_cached(512, f32=True)
    m.clear()


def test_get_or_build_many_builds_what_is_absent_in_one_call():
    from daft_tpu.core.series import Series

    m = manager()
    m.clear()
    a, b, c = (Series.from_pylist([float(i)], n) for i, n in enumerate("abc"))
    m.get_or_build(b, ("t",), (), lambda: "b-was-here")
    calls = []

    def build(missing):
        calls.append(list(missing))
        return [f"built-{i}" for i in missing]

    slots = [(a, ("t",), 0), (b, ("t",), 0), (c, ("t",), 5)]
    assert m.get_or_build_many(slots, build) == ["built-0", "b-was-here", "built-2"]
    assert calls == [[0, 2]]
    assert m.get_or_build_many(slots, build) == ["built-0", "b-was-here", "built-2"]
    assert calls == [[0, 2]]                              # all found: no build
    assert m.get_or_build(c, ("t",), (), lambda: "no") == "built-2"
    assert m.entry_count() == 3
    m.clear()
