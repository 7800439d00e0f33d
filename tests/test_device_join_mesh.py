"""The single chip's join dispatch on every shard of a mesh (`mesh_devices`
> 1 in ops/device_join.py), on the CPU's virtual devices: the answers of one
chip, the run-wide tables of one chip once the shards' are added up, the one
chip's order where groups straddle shards and sort keys tie across chips, and
a ceiling that is held to a chip's share of the ids."""

import numpy as np
import pytest

import jax

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.ops import counters

import test_device_join as tj
import test_mesh_join as tm

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 (virtual) devices: see conftest")

MESH = 4
_MORSEL = tj._MORSEL


def _run(q, mesh_devices):
    """(answer, counters) of one forced device run over `mesh_devices`."""
    counters.reset()
    counters.rejections.clear()
    with execution_config_ctx(device_mode="on", morsel_size_rows=_MORSEL,
                              pipeline_mode="force", mesh_devices=mesh_devices):
        out = q().to_pydict()
    return out, counters.snapshot()


def _ungrouped(t):
    return (t["orders"].where(col("o_orderdate") < tj._days(1995, 3, 15))
            .join(t["lineitem"].where(col("l_returnflag") == "R"),
                  left_on="o_orderkey", right_on="l_orderkey")
            .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"),
                 col("l_discount").count().alias("n")))


def _ungrouped_numpy(t):
    """The ungrouped join-aggregate in numpy float64 on the tables' columns."""
    o, li = t["orders"].to_pydict(), t["lineitem"].to_pydict()
    kept = {k for k, d in zip(o["o_orderkey"], o["o_orderdate"])
            if d < tj._days(1995, 3, 15)}
    keep = np.array([k in kept and f == "R"
                     for k, f in zip(li["l_orderkey"], li["l_returnflag"])])
    price, disc = np.array(li["l_extendedprice"]), np.array(li["l_discount"])
    return float((price * (1 - disc))[keep].sum()), int(keep.sum())


one_bucket = tj.one_bucket      # (a bucket a shard a dispatch: the tests that count one)


@pytest.fixture(scope="module")
def topn_tables():
    # thirteen morsels: a mesh dispatch takes four, so the run ends on a
    # dispatch whose last shards are short or empty
    return tj._topn_tables(n_l=_MORSEL * 13 - 100)


@pytest.fixture(scope="module")
def tpch_like():
    return tj._tpch_like()


# ---- (a) the answers are the one chip's ----------------------------------------------------

@pytest.mark.usefixtures("one_bucket")
@pytest.mark.parametrize("shape", ["q3", "q10", "q5", "ungrouped"])
def test_sharded_join_answers_as_one_chip_does(topn_tables, tpch_like, shape):
    """q3-, q10-, q5-shaped joins and an ungrouped join-aggregate over a fact
    of several batches under mesh_devices=4: the host engine's rows in its
    order, the one chip's answer, every join dispatch spanning four devices."""
    if shape == "q5":
        q = lambda: tj._q5_shaped(tpch_like)
    elif shape == "ungrouped":
        q = lambda: _ungrouped(topn_tables)
    else:
        q = lambda: {"q3": tj._topn_q3, "q10": tj._topn_q10}[shape](topn_tables)
    host = tj._host_answer(q)
    one, c1 = _run(q, 1)
    four, c4 = _run(q, MESH)
    assert c1.get("device_join_mesh_batches", 0) == 0 and c1["device_join_batches"] > 0
    joins = c4["device_join_batches"]
    assert joins > 0 and c4["device_join_mesh_batches"] == joins, counters.rejections
    assert c4["device_join_mesh_shards"] == MESH * joins
    assert c4["device_mesh_batches"] == joins and c4["mesh_join_runs"] == 1
    tj._assert_close(host, one)
    tj._assert_close(host, four)
    if shape in ("q3", "q10"):
        limit = 10 if shape == "q3" else 20
        assert four == one, "ties included: the merge of K rows a chip is the one chip's order"
        assert c4["device_topn_runs"] == 1 and c4["device_join_topn_batches"] == joins > 1
        assert c4["device_topn_fetched_rows"] == MESH * limit
        assert c4["device_topn_combine_bytes"] > 0 and not c1.get("device_topn_combine_bytes")
        assert c1["device_join_batches"] > joins, "a mesh dispatch covers a bucket a shard"
    if shape == "ungrouped":
        revenue, n = _ungrouped_numpy(topn_tables)
        assert four["n"] == [n]
        assert four["revenue"][0] == pytest.approx(revenue, rel=1e-6)


@pytest.mark.usefixtures("one_bucket")
@pytest.mark.parametrize("shape", ["q3", "q5"])
def test_a_sharded_joins_filter_values_are_arguments(tpch_like, shape):
    """q3- and q5-shaped joins under mesh_devices=4 with one value set after
    another (a SEGMENT or REGION, a DATE, a value the dictionary lacks): the
    host tier's answers, every join dispatch spanning the four devices, the
    verdict made once a query whole on every chip, and from the second value
    set on no slot rebuilt, no program traced, nothing built and nothing
    uploaded: the pack is never copied to the chips again for a value."""
    from daft_tpu.device.residency import manager

    manager().clear()
    make = tj._q3_shaped if shape == "q3" else tj._q5_shaped
    name = "segment" if shape == "q3" else "region"
    other = "MACHINERY" if shape == "q3" else "EUROPE"
    answers = []
    for k, kw in enumerate([dict(), {name: other}, {name: other, "cut": (1994, 6, 1)},
                            {name: "NO SUCH VALUE"}, dict()]):
        q = lambda: make(tpch_like, **kw)
        four, c4 = _run(q, MESH)
        tj._assert_close(tj._host_answer(q), four)
        joins = c4["device_join_batches"]
        assert joins > 0 and c4["device_join_mesh_batches"] == joins, counters.rejections
        assert c4["join_filter_literal_args"] == (2 if shape == "q3" else 3)
        if k:
            assert not any(c4.get(c, 0) for c in (
                "hbm_literal_rebuilds", "join_filter_program_traces", "join_provision_traces",
                "device_stage_program_traces", "hbm_cache_misses", "hbm_h2d_bytes")), (kw, c4)
        answers.append(four)
    assert answers[0] == answers[4] and answers[0] != answers[1] != answers[2]
    assert not next(iter(answers[3].values()))
    manager().clear()


# ---- (b) the shards' tables add up to the one chip's ---------------------------------------

@pytest.mark.usefixtures("one_bucket")
def test_the_four_shards_tables_add_up_to_the_one_chips(topn_tables, monkeypatch):
    """q3's run-wide tables at the run's end: every chip's set, added up over
    the chips, is the one chip's. Rows and first-row positions exactly (a
    count is a sum of ones, and a row's position in the run's stream does not
    depend on the shard it fell in); a sum within 1e-6 of its size: a chip's
    share is a float32 pair whose low word carries what the high word's 24
    bits drop, and shares added in another order differ in the pair's last
    bits."""
    import daft_tpu.ops.device_join as dj

    seen = {}
    real = dj.DeviceJoinTopNRun._finalize_run_wide

    def spy(self):
        cap, ndev = self._cap, self.mesh_devices
        seen[ndev] = (cap, jax.device_get(
            {k: self._tables[k] for k in ("hi", "lo", "first")}))
        return real(self)

    monkeypatch.setattr(dj.DeviceJoinTopNRun, "_finalize_run_wide", spy)
    one, _c = _run(lambda: tj._topn_q3(topn_tables), 1)
    four, _c = _run(lambda: tj._topn_q3(topn_tables), MESH)
    assert one == four
    (cap, t1), (cap4, t4) = seen[1], seen[MESH]
    assert cap == cap4

    def of_ids(x, ndev):
        """[ndev, cap]: a chip's table is the one chip's length; cut the tail."""
        x = np.asarray(x)
        return x.reshape(ndev, len(x) // ndev)[:, :cap]

    for j in range(len(t1["hi"])):
        whole = (of_ids(t1["hi"][j], 1).astype(np.float64)
                 + of_ids(t1["lo"][j], 1).astype(np.float64))[0]
        shares = (of_ids(t4["hi"][j], MESH).astype(np.float64)
                  + of_ids(t4["lo"][j], MESH).astype(np.float64))
        assert (shares != 0).any(axis=1).all(), "every chip added rows of its own"
        np.testing.assert_allclose(shares.sum(axis=0), whole, rtol=1e-6, atol=1e-6)
        if j == 0:      # the rows a group took in: a count
            np.testing.assert_array_equal(shares.sum(axis=0), whole)
    np.testing.assert_array_equal(of_ids(t4["first"], MESH).min(axis=0),
                                  of_ids(t1["first"], 1)[0])


# ---- (b2) every shard chooses its own form ---------------------------------------------------

@pytest.mark.usefixtures("one_bucket")
@pytest.mark.parametrize("case,kept", [
    # (a dispatch is four morsels, a morsel a shard: kept rows by dispatch and shard)
    ("one_shard_over_k", [40, tj._K + 1, tj._K, 90] * 2),
    ("all_under_k", [40, tj._K, 1, 90] * 2),
    ("one_shard_empty", [0, 700, tj._K - 1, 5, tj._K, 0, 0, 3]),
])
def test_each_shard_compacts_or_scatters_by_its_own_rows(case, kept, monkeypatch):
    """Over the mesh a shard whose kept rows pass K takes the scatter form
    while the others compact theirs in the same dispatch: a chip's tables are
    what the scatter form alone leaves on it, the answer is the one chip's,
    and the counters read a count a chip. A chip folds its own partial into
    its own tables, in the dispatches in which ITS shard was not dense, and
    leaves it all zeros."""
    import daft_tpu.ops.grouped_stage as gs

    t, kept_ids = tj._compaction_fact(kept)
    dense, compact, _scatter = tj._forms_of(kept_ids)
    host = tj._host_answer(lambda: tj._topn_q3(t))
    seen, partials = [], []
    tj._spy_run_wide_tables(monkeypatch, seen, partials)
    four, c4 = _run(lambda: tj._topn_q3(t), MESH)
    assert c4["device_topn_runs"] == 1 and c4["device_join_mesh_batches"] == len(kept) // MESH, \
        counters.rejections
    assert c4.get("join_topn_compact_batches", 0) == compact // MESH
    assert c4.get("join_topn_folds", 0) == (len(kept) - dense) // MESH      # (a count a chip)
    assert partials == [True] * (len(kept) // MESH)
    tj._assert_close(host, four)
    one, c1 = _run(lambda: tj._topn_q3(t), 1)
    assert one == four and c1.get("join_topn_compact_batches", 0) == compact
    assert c1.get("join_topn_folds", 0) == len(kept) - dense

    monkeypatch.setattr(gs, "COMPACT_SHARE", 1 << 30)
    gs._STAGE_CACHE.clear()
    try:
        plain, c = _run(lambda: tj._topn_q3(t), MESH)
        assert c.get("join_topn_compact_batches", 0) == 0
    finally:
        gs._STAGE_CACHE.clear()
    assert plain == four
    (_b, got), (_b1, _one), (_b4, want) = seen
    assert int(np.sum(got["compact"])) == compact and int(np.sum(got["dense"])) == dense
    assert np.array_equal(got["dense"], want["dense"]) and not np.any(want["compact"])
    # shard s of every dispatch is the same chip: its forms are its batches'
    for s in range(MESH):
        mine = kept_ids[s::MESH]
        assert (int(got["dense"][s]), int(got["compact"][s])) == tj._forms_of(mine)[:2]
        assert int(got["folds"][s]) == int(want["folds"][s]) == tj._folds_of(mine, 1)
        chip = lambda tables: {
            k: [np.split(np.asarray(x), MESH)[s] for x in v] if isinstance(v, tuple)
            else np.split(np.asarray(v), MESH)[s] for k, v in tables.items()
            if k in ("hi", "lo", "first")}
        tj._assert_tables_agree(chip(got), chip(want), mine, 1)


# ---- (b3) a dispatch of DISPATCH_SEGMENTS buckets a shard --------------------------------------

_LONG = 45      # morsels: a dispatch of 8 a shard takes 32, the tail's last shard is part padding


@pytest.fixture(scope="module")
def long_tables():
    return tj._topn_tables(n_l=_MORSEL * _LONG - 100)


@pytest.mark.parametrize("shape", ["q3", "q10", "q5", "ungrouped"])
def test_a_long_sharded_dispatch_answers_as_a_bucket_a_shard_does(long_tables, shape, monkeypatch):
    """Over a resident fact of 45 morsels a sharded join dispatch covers
    DISPATCH_SEGMENTS buckets a shard (32 morsels, then a tail of 13 over
    shards of 8, 5 and 0): two ranges of the table, cut by the driver, two
    dispatches that each span the four devices,
    the host engine's answer, the one chip's answer and the answer of a
    bucket a shard (12 dispatches); a repeat builds nothing."""
    import daft_tpu.ops.grouped_stage as gs

    if shape == "q5":
        t = tj._tpch_like(n_l=_MORSEL * _LONG - 100)
        q = lambda: tj._q5_shaped(t)
    elif shape == "ungrouped":
        q = lambda: _ungrouped(long_tables)
    else:
        q = lambda: {"q3": tj._topn_q3, "q10": tj._topn_q10}[shape](long_tables)
    host = tj._host_answer(q)
    assert gs.DISPATCH_SEGMENTS == 8
    four, c4 = _run(q, MESH)
    assert c4["join_resident_ranges"] == 2 and c4.get("coalesce_morsels_in", 0) == 0
    assert c4["device_join_batches"] == c4["device_join_mesh_batches"] == 2, counters.rejections
    assert c4["device_join_mesh_shards"] == 2 * MESH and c4["hbm_cache_misses"] > 0
    tj._assert_close(host, four)
    again, ca = _run(q, MESH)
    assert again == four and ca.get("hbm_cache_misses", 0) == 0
    one, c1 = _run(q, 1)
    assert c1["device_join_batches"] == tj._dispatches(_LONG, 8)
    tj._assert_close(host, one)
    monkeypatch.setattr(gs, "DISPATCH_SEGMENTS", 1)
    short, cs = _run(q, MESH)
    assert cs["device_join_mesh_batches"] == tj._dispatches(_LONG, 1, MESH)
    if shape in ("q3", "q10"):
        assert four == one == short, "ties included"
        assert c4["device_topn_runs"] == 1 and c4["device_join_topn_batches"] == 2
    else:
        tj._assert_close(short, four)


@pytest.mark.parametrize("shape", ["q3", "q5"])
def test_the_driver_cuts_the_sharded_ranges_the_coalescer_flushed(long_tables, shape, monkeypatch):
    """Over four shards the driver hands on the two ranges a coalescer at the
    sharded resident target made of the table's 45 morsels (32, and the tail
    of 13), views of the table's own columns; no Project stage starts, nothing
    is fanned out over the pool, and no morsel reaches a coalescer."""
    if shape == "q5":
        t = tj._tpch_like(n_l=_MORSEL * _LONG - 100)
        q = lambda: tj._q5_shaped(t)
    else:
        t, q = long_tables, lambda: tj._topn_q3(long_tables)
    fed = []
    tj._spy_fed_batches(monkeypatch, fed)
    spawned, fanned = tj._spy_pipeline(monkeypatch)
    _out, c = _run(q, MESH)
    assert c["join_resident_ranges"] == c["device_join_mesh_batches"] == 2
    assert c.get("coalesce_morsels_in", 0) == c.get("dispatch_coalesced", 0) == 0
    assert "Project" not in spawned and fanned == []
    glued = []
    coal = tj._coalescer(glued, t["lineitem"].count_rows(), shards=MESH)
    for m in tj._resident_morsels(t["lineitem"], _MORSEL):
        coal.add(m)
    coal.close()
    want = tj._views_of(t["lineitem"], glued)
    assert want == [(0, 32 * _MORSEL), (32 * _MORSEL, 13 * _MORSEL - 100)]
    assert tj._views_of(t["lineitem"], fed) == want


class _ProgramsAskedFor:
    """Programs asked of the backend while `on`, each counted ONCE whether it
    was compiled or found in the persistent cache: JAX reports
    `backend_compile_duration` around the look-up and the compile together
    (jax/_src/interpreters/pxla.py), so a hit fires it too. Counting
    `/jax/compilation_cache/cache_hits` beside it, as this did, counted a
    hit twice, and which programs hit is the machine's load: an entry is
    written only where its compile took a second (`jax_persistent_cache_min_
    compile_time_secs`), so under six busy workers a first cold pass wrote
    three programs that a later cold pass then found, 56 against 59.
    (jax.monitoring has no way to take a listener off again: one for the
    module.)"""

    def __init__(self):
        from jax import monitoring

        self.on, self.n = False, 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, _secs, **_kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def during(self, fn):
        self.on, self.n = True, 0
        try:
            fn()
        finally:
            self.on = False
        return self.n


@pytest.fixture(scope="module")
def asked_for():
    return _ProgramsAskedFor()


@pytest.mark.parametrize("mesh", [1, MESH])
def test_the_ranges_changed_no_program(long_tables, asked_for, mesh, monkeypatch):
    """q3, q5 and q10 over one chip and over a mesh of four ask for the same
    programs whichever road the fact and the dimensions take. With every
    cache dropped the pipeline's road (what a plan that is no select takes,
    and what every join took before) asks for N programs; the driver's own
    ranges, run next with those programs still there, ask for none (every
    program of theirs is one of the N); dropped again and run cold they ask
    for N (and so for each of them)."""
    from daft_tpu.device.residency import manager
    from daft_tpu.execution import executor

    t5 = tj._tpch_like(n_l=_MORSEL * _LONG - 100)
    queries = [lambda: tj._topn_q3(long_tables), lambda: tj._q5_shaped(t5),
               lambda: tj._topn_q10(long_tables)]

    def passes():
        for q in queries:
            _run(q, mesh)

    def cold():
        manager().clear()
        jax.clear_caches()
        return asked_for.during(passes)

    with monkeypatch.context() as piped:
        piped.setattr(executor, "_resident_select", lambda plan: None)
        piped_cold = cold()
        assert counters.snapshot().get("join_resident_ranges", 0) == 0
    assert piped_cold > 0
    assert asked_for.during(passes) == 0, "a range's program is a glued run's"
    assert counters.snapshot()["join_resident_ranges"] == tj._dispatches(_LONG, 8, mesh)
    assert cold() == piped_cold
    manager().clear()


def test_a_fallback_after_a_sharded_range_answers_from_the_host_plan(monkeypatch):
    """A DeviceFallback at the second sharded range of a resident fact: the
    host plan's answer, and no stage thread left behind."""
    import daft_tpu.ops.device_join as dj
    from daft_tpu.ops.grouped_stage import DeviceFallback

    t = tj._tpch_like(n_l=_MORSEL * _LONG - 100)
    q = lambda: tj._q5_shaped(t)
    host = tj._host_answer(q)
    real, fed = dj.DeviceJoinGroupedRun.feed_batch, []

    def feed_batch(self, batch):
        if fed:
            raise DeviceFallback("the second range will not go")
        fed.append(batch.num_rows)
        return real(self, batch)

    monkeypatch.setattr(dj.DeviceJoinGroupedRun, "feed_batch", feed_batch)
    answer, c = _run(q, MESH)
    assert fed == [32 * _MORSEL] and c["join_resident_ranges"] == 1
    assert any("device fallback" in k for k in counters.rejections), counters.rejections
    tj._assert_close(host, answer)
    assert not tj._stage_threads_left()


def test_every_segment_of_every_shard_chooses_its_own_form(monkeypatch):
    """A sharded dispatch of sixteen morsels, four segments a shard (the fact
    is twenty-three: a dispatch is never all of it), and the tail's seven,
    two segments a shard: dense segments (nothing kept), compacted ones and
    scattered ones side by side on one chip; the counts over the chips are
    the segments' own, a shard's padding is never walked, a chip folds once
    in each dispatch in which one of ITS segments was not dense, and the
    answer is the one chip's."""
    kept = [40, tj._K + 1, 0, tj._K,   600, 3, tj._K - 1, 0,
            tj._K + 5, tj._K + 9, 1, 2,   0, 0, 90, tj._K + 2,
            7, 0,   tj._K + 1, 30,   0, tj._K + 3,   tj._K]    # the tail: the last shard's second segment is padding
    t, kept_ids = tj._compaction_fact(kept)
    dense, compact, _scatter = tj._forms_of(kept_ids)
    assert dense and compact and _scatter
    host = tj._host_answer(lambda: tj._topn_q3(t))
    seen, partials = [], []
    tj._spy_run_wide_tables(monkeypatch, seen, partials)
    four, c4 = _run(lambda: tj._topn_q3(t), MESH)
    assert c4["device_topn_runs"] == 1 and c4["device_join_mesh_batches"] == 2, counters.rejections
    assert partials == [True, True]
    tj._assert_close(host, four)
    (_b, got), = seen
    per, tail = 4, 2    # morsels a shard: sixteen over four shards of a 8,192-row bucket, then seven over 4,096
    for s in range(MESH):
        first = kept_ids[s * per:(s + 1) * per]
        last = kept_ids[MESH * per + s * tail:MESH * per + (s + 1) * tail]
        assert (int(got["dense"][s]), int(got["compact"][s])) == tj._forms_of(first + last)[:2], s
        assert int(got["folds"][s]) == tj._holds_sparse(first) + tj._holds_sparse(last), s
    assert int(np.sum(got["dense"])) == dense and int(np.sum(got["compact"])) == compact
    assert c4["join_topn_folds"] == int(np.sum(got["folds"])) // MESH == 2     # (a count a chip)
    one, c1 = _run(lambda: tj._topn_q3(t), 1)
    # the one chip's dispatch is eight other segments than a shard's four, so
    # other rows of an id meet in a float32 partial: keys, dates and order
    # equal, a sum within a float32 ulp of itself a dispatch (the one chip's
    # three), as _assert_tables_agree holds the tables
    assert list(one) == list(four)
    for name in one:
        if name == "revenue":
            for a, b in zip(one[name], four[name], strict=True):
                assert abs(a - b) <= 3 * float(np.spacing(np.float32(abs(a)))), (a, b)
        else:
            assert one[name] == four[name], name
    assert c1["join_topn_compact_batches"] == compact
    assert c1["join_topn_folds"] == tj._folds_of(kept_ids, 8) == 3


# ---- (b4) the dense form's two digits and its first rows, a shard at a time -------------------

@pytest.mark.parametrize("per_shard", [1, 2], ids=["one_bucket", "segments"])
@pytest.mark.parametrize("case", tj._DENSE_CASES)
def test_the_shards_dense_forms_add_up_to_the_float64_reference(case, per_shard, monkeypatch):
    """Every shard takes the dense form over its own morsels (one a dispatch,
    or two walked as segments), its first rows through the product where its
    segment's ids are in order and by the masked minimum where not: the
    chips' tables add up to the float64 reference, their first rows' least is
    the stream's, the counts are the segments' own, and the answer is the
    one chip's, ties included."""
    import daft_tpu.ops.grouped_stage as gs

    morsels = 2 * MESH * per_shard
    if per_shard == 1:
        monkeypatch.setattr(gs, "DISPATCH_SEGMENTS", 1)
    keys, kept = tj._dense_rows(case, morsels, _MORSEL)
    dense, ordered = tj._dense_verdicts(keys, kept, _MORSEL)
    assert dense == morsels and ordered == (0 if case.endswith("unordered") else morsels)
    t, revenue = tj._dense_fact(keys, kept)
    host = tj._host_answer(lambda: tj._topn_q3(t))
    seen = []
    tj._spy_run_wide_tables(monkeypatch, seen)
    four, c4 = _run(lambda: tj._topn_q3(t), MESH)
    assert c4["device_topn_runs"] == 1 and c4["device_join_mesh_batches"] == 2, counters.rejections
    assert c4.get("join_topn_ordered_batches", 0) == ordered // MESH      # (a count a chip)
    assert c4.get("join_topn_compact_batches", 0) == 0
    assert c4.get("join_topn_folds", 0) == 0        # (no sparse segment: no chip folds)
    tj._assert_close(host, four)
    one, c1 = _run(lambda: tj._topn_q3(t), 1)
    assert one == four and c1.get("join_topn_ordered_batches", 0) == ordered

    (_b4, got), (_b1, alone) = seen
    assert got["ordered"].shape == (MESH,)
    assert (int(np.sum(got["dense"])), int(np.sum(got["ordered"])), int(np.sum(got["compact"]))) \
        == (dense, ordered, 0)
    assert got["folds"].shape == (MESH,) and not np.any(got["folds"])
    assert not any(np.any(p) for p in got["part"])
    assert (int(alone["dense"]), int(alone["ordered"])) == (dense, ordered)
    length = len(alone["first"])
    rows, sums, first = tj._dense_reference(keys, kept, revenue, length)
    chips = lambda x: np.asarray(x, np.float64).reshape(MESH, length)
    total = lambda k: (chips(got["hi"][k]) + chips(got["lo"][k])).sum(axis=0)
    np.testing.assert_array_equal(total(0), rows)
    np.testing.assert_array_equal(total(1), rows)
    np.testing.assert_allclose(total(2), sums, rtol=2.0 ** -22, atol=0)
    np.testing.assert_array_equal(
        np.asarray(got["first"], np.int64).reshape(MESH, length).min(axis=0), first)
    np.testing.assert_array_equal(np.asarray(alone["first"], np.int64), first)


# ---- (c) groups that straddle shards, keys that tie across chips -----------------------------

@pytest.mark.usefixtures("one_bucket")
def test_groups_straddle_shards_and_ties_fall_as_on_one_chip(topn_tables):
    """The fact is sorted by order key and an order has several lines, so
    orders straddle the shard boundaries of a dispatch; revenues are whole
    numbers that tie, and the tied groups' ids lie in different chips'
    slices: the merge of the chips' winners gives the one chip's rows in the
    one chip's order, with and without an offset."""
    keys = topn_tables["lineitem"].to_pydict()["l_orderkey"]
    per = _MORSEL    # rows a shard of a full dispatch
    cuts = [b for b in range(per, len(keys), per) if keys[b - 1] == keys[b]]
    assert cuts, "an order's lines lie on both sides of a shard boundary"
    for shape in (tj._topn_q3, tj._topn_q10):
        for offset in (0, 3):
            q = lambda: shape(topn_tables, offset=offset)
            host = tj._host_answer(q)
            revenue = host["revenue"]
            assert len(set(revenue)) < len(revenue), "the sort key ties"
            one, _c = _run(q, 1)
            four, c4 = _run(q, MESH)
            assert c4["device_topn_runs"] == 1, counters.rejections
            assert four == one
            tj._assert_close(host, four)
    # the winners' ids come from more than one chip's slice of the id space
    orders = topn_tables["orders"].to_pydict()["o_orderkey"]
    from daft_tpu.ops.stage import pad_bucket

    part = pad_bucket(len(orders)) // MESH
    row_of = {k: i for i, k in enumerate(orders)}
    four, _c = _run(lambda: tj._topn_q3(topn_tables), MESH)
    assert len({row_of[k] // part for k in four["l_orderkey"]}) > 1


# ---- (d) the ceiling is a chip's ---------------------------------------------------------------

@pytest.mark.usefixtures("one_bucket")
def test_the_table_ceiling_is_held_to_a_chips_share_of_the_ids(topn_tables, monkeypatch):
    """A dimension whose padded rows pass TOPN_RUN_MAX_SEGMENTS is refused on
    one chip with the ceiling's reason (the run is then held to one fact
    batch, and a fact of several goes to the host plan) and accepted on a mesh
    of four, where a chip combines and selects over a quarter of the ids."""
    import daft_tpu.ops.device_join as dj
    from daft_tpu.ops.stage import pad_bucket

    cap = pad_bucket(len(topn_tables["orders"].to_pydict()["o_orderkey"]))
    monkeypatch.setattr(dj, "TOPN_RUN_MAX_SEGMENTS", cap // MESH)
    q = lambda: tj._topn_q3(topn_tables)
    host = tj._host_answer(q)
    one, c1 = _run(q, 1)
    assert c1.get("device_topn_runs", 0) == 0
    why = " ".join(entry for _site, entry in counters.rejection_log)
    assert "over the run-wide table ceiling" in why and "multi-batch fact" in why, why
    tj._assert_close(host, one)     # the host plan's answer
    four, c4 = _run(q, MESH)
    assert c4["device_topn_runs"] == 1 and c4["device_join_mesh_batches"] > 1, \
        counters.rejections
    assert c4["device_topn_fetched_rows"] == MESH * 10
    tj._assert_close(host, four)
    # a chip's share over the ceiling too: refused on the mesh with the same reason
    monkeypatch.setattr(dj, "TOPN_RUN_MAX_SEGMENTS", cap // MESH // 2)
    _, c4 = _run(q, MESH)
    assert c4.get("device_topn_runs", 0) == 0 and c4.get("device_join_mesh_batches", 0) == 0


# ---- (e) what the sharded dispatch declines runs on one chip -------------------------------

WIDE = 8    # (conftest's eight virtual devices)


star = tm.star      # (a fact of one batch with int64 values past 2^53, a dimension grouped by a column that is not its key)


def _declined(shape, tpch_like, star):
    """(query, site, config, sharded_join_reason's words) of a join the
    sharded dispatch declines: group codes that need a host factorization (as
    many groups as orders), a fused TopN whose ids hold for one batch (grouped
    by a dimension's column that is not its key), a forced Pallas probe."""
    t, (fact, dim) = tpch_like, star
    joined = lambda: fact.join(dim, left_on="fk", right_on="dk")
    if shape == "host_codes":
        q = lambda: (t["orders"].join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
                     .groupby("o_orderkey").agg(col("l_extendedprice").sum().alias("s"))
                     .sort("o_orderkey"))
        return q, "join agg", {}, "the group codes need a host factorization of every batch"
    if shape == "one_batch_topn":
        q = lambda: (joined().groupby("grp").agg(col("qty").sum().alias("s"))
                     .sort("s", desc=True).limit(3))
        return q, "join topn", {}, "no dimension's key with its own columns spans the group-by"
    q = lambda: (joined().groupby("grp")
                 .agg(col("qty").sum().alias("s"), col("big").sum().alias("sb")).sort("grp"))
    return q, "join agg", {"pallas_mode": "on"}, "a forced Pallas hash probe runs on one chip"


def _assert_answers(host, got):
    assert host.keys() == got.keys()
    for name, want in host.items():
        if name == "s" and isinstance(want[0], float):
            np.testing.assert_allclose(got[name], want, rtol=1e-6)
        else:
            assert got[name] == want, name      # (2^53-scale int64 sums included)


SHAPES = ["host_codes", "one_batch_topn", "forced_pallas"]


@pytest.mark.parametrize("shape", SHAPES)
def test_a_join_the_sharded_dispatch_declines_runs_on_one_chip(tpch_like, star, shape):
    """A forced mesh of eight and a join the sharded dispatch declines: no
    dispatch spans the mesh, the join runs on one chip and answers as the
    host does, and the rejection log and the placement record carry
    sharded_join_reason's words."""
    from daft_tpu.observability import placement

    q, site, extra, words = _declined(shape, tpch_like, star)
    host = tj._host_answer(q)
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=WIDE, device_min_rows=1, **extra):
        with placement.query_scope() as scope:
            got = q().to_pydict()
    snap = counters.snapshot()
    assert snap.get("device_join_mesh_batches", 0) == 0 and snap.get("mesh_join_runs", 0) == 0
    assert snap.get("mesh_dispatches", 0) == 0 and snap.get("mesh_unavailable_fallbacks", 0) == 0
    assert snap["device_join_batches"] > 0, counters.rejections
    assert snap.get("device_topn_runs", 0) == (shape == "one_batch_topn")
    assert snap.get("pallas_probe_dispatches", 0) == (shape == "forced_pallas")
    _assert_answers(host, got)
    assert ("runtime", f"{site}: not sharded over the mesh ({words})") in counters.rejection_log
    rec, = [r for r in scope.to_dicts() if r["site"] == site]
    assert rec["chosen"] == "device" and rec["forced"]
    assert rec["reason"] == f"not sharded over the mesh: {words}"
    assert words in placement.render(scope.records())    # (explain_placement()'s text)


@pytest.mark.parametrize("shape", SHAPES)
def test_auto_prices_a_declined_join_as_a_one_chip_host_does(tpch_like, star, shape, monkeypatch):
    """`auto` on a (simulated) accelerator host of eight devices, under terms
    that make a mesh win every join it is offered (tests/test_mesh_join.py):
    a declined join is priced chip against host, with no mesh arm, and where
    the chip wins its run is built for one device (the test stops there: what
    a chip then answers is the test above, and the kernels of a simulated
    chip do not lower)."""
    import daft_tpu.ops.device_join as dj
    from daft_tpu.execution import executor
    from daft_tpu.observability import placement
    from daft_tpu.ops import costmodel

    q, site, extra, words = _declined(shape, tpch_like, star)
    # (and a host slow enough that one chip can win too)
    pins = dict(tm._MESH_WINS_PINS, DAFT_TPU_COST_HOST_AGG="1e4", DAFT_TPU_COST_HOST_PROBE="1e4")
    for k, v in pins.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    built = []

    class _Decided(Exception):
        pass

    def stop(self, stage, ctx, mesh_devices=1, *a, **k):
        built.append(mesh_devices)
        raise _Decided

    monkeypatch.setattr(dj.DeviceJoinGroupedRun, "__init__", stop)
    costmodel.reset_calibration()
    executor._DECISION_CACHE.clear()
    try:
        counters.reset()
        with execution_config_ctx(device_mode="auto", mesh_devices=0, device_min_rows=1, **extra):
            with placement.query_scope() as scope:
                try:
                    q().to_pydict()
                except _Decided:
                    pass
        rec, = [r for r in scope.to_dicts() if r["site"] == site]
        assert rec["chosen"] in ("device", "host") and "mesh" not in rec
        assert rec["device"]["total"] > 0 and rec["host"]["total"] > 0
        assert rec["reason"] == f"not sharded over the mesh: {words}"
        assert built == ([1] if rec["chosen"] == "device" else [])
        if shape != "host_codes":   # (as many groups as orders: the host's under any terms)
            assert rec["chosen"] == "device"
        assert ("runtime", f"{site}: not sharded over the mesh ({words})") in counters.rejection_log
    finally:
        costmodel.reset_calibration()
        executor._DECISION_CACHE.clear()


@pytest.mark.parametrize("shape", SHAPES)
def test_a_declined_joins_repeat_uploads_nothing(tpch_like, star, shape):
    """The one chip a declined join runs on keeps its planes: the repeat of
    the query under the forced mesh moves no byte to the device and builds no
    slot."""
    from daft_tpu.observability.metrics import registry

    q, _site, extra, _words = _declined(shape, tpch_like, star)
    with execution_config_ctx(device_mode="on", mesh_devices=WIDE, device_min_rows=1, **extra):
        first = q().to_pydict()
        h2d, misses = registry().get("hbm_h2d_bytes"), registry().get("hbm_cache_misses")
        joins = registry().get("device_join_batches")
        again = q().to_pydict()
    assert again == first and registry().get("device_join_batches") > joins
    assert registry().get("hbm_h2d_bytes") == h2d, "the repeat uploaded planes"
    assert registry().get("hbm_cache_misses") == misses
