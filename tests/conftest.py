"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh (SURVEY.md §7) so multi-chip
sharding paths are exercised without TPU hardware. The suite is hermetic: the
platform is pinned here as well as by the JAX_PLATFORMS=cpu the tier-1 command
exports, so a bare `pytest tests/` on a machine with a chip still runs on CPU.
"""

import os
import sys

# XLA_FLAGS is read at backend-init time, so mutating it here still works.
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


# Tests of the benchmark's own files that pin entries of `BENCHMARK.json` by
# POSITION (counted from the end of a list) or hold a cell's whole set of
# per-layer metrics. A later PR may add entries only at the end, and may not
# edit those files (they are the benchmark's), so each stops holding when the
# next PR appends; what else it asserts is kept BY NAME elsewhere. The mark
# is strict: once the benchmark is repaired and a pinned test passes again,
# the suite fails until its entry here is taken out. A `benchmark` PR should
# move the pins into the tests and drop this hook (ROADMAP M6).
_POSITION_PINS = {
    "test_bench_mesh.py::test_the_cell_is_the_benchmarks_one_four_chip_cell":
        "pins PR 32's entries to the end of BENCHMARK.json's lists; PR 34's entries "
        "follow them (kept by name in test_bench_setup.py)",
    "test_bench_adhoc.py::test_the_entries_stand_at_the_end_of_their_lists":
        "pins PR 34's entries to the end of per_layer and the ad-hoc cell's metrics to "
        "a closed set; PR 36's setup.* entries follow and list the cell "
        "(kept by name in test_bench_setup.py)",
    "test_bench_adhoc.py::test_the_four_chip_cells_entries_are_as_they_were":
        "pins PR 32's mesh.* entries 18 from the end and the four-chip cell's metrics "
        "to a closed set; PR 36's setup.* entries follow and list the cell "
        "(kept by name in test_bench_setup.py)",
    "test_bench_joins10.py::test_the_cell_and_its_metrics_are_appended_entries":
        "counts ONE four-chip cell in BENCHMARK.json; PR 41's tpch_sf30_mesh4.joins is the "
        "second (what else it asserts is kept by name in test_bench_joins_mesh.py::"
        "test_the_one_chip_join_cells_entries_are_as_they_were)",
    "test_bench_setup.py::test_the_four_chip_cells_entries_are_as_they_were":
        "holds the benchmark's four-chip cells to tpch_sf30_mesh4.scanagg alone; PR 41's "
        "tpch_sf30_mesh4.joins is the second (what else it asserts is kept by name in "
        "test_bench_joins_mesh.py::test_the_four_chip_scan_cells_entries_are_as_they_were)",
    "test_bench_joins_mesh.py::test_the_one_chip_join_cells_entries_are_as_they_were":
        "pins PR 41's cell and configuration to the end of BENCHMARK.json's lists; PR 45's "
        "tpch_sf10.adhoc_joins follows them (what else it asserts is kept by name in "
        "test_bench_adhoc_joins.py::test_the_join_cells_entries_are_as_they_were)",
    "test_bench_adhoc_joins.py::test_the_cell_and_its_metrics_are_entries_by_name":
        "counts EIGHT cells in BENCHMARK.json; PR 49's tpch_sf10.filtered_joins is the ninth "
        "(what else it asserts is kept by name in test_bench_filtered_joins.py::"
        "test_the_ad_hoc_join_cells_entries_are_as_they_were)",
    "test_bench_adhoc_joins.py::test_the_join_cells_entries_are_as_they_were":
        "pins PR 45's cell, configuration and adhocjoin.* metrics to the end of BENCHMARK.json's "
        "lists; PR 49's tpch_sf10.filtered_joins follows them (what else it asserts is kept by "
        "name in test_bench_filtered_joins.py::"
        "test_the_ad_hoc_join_cells_entries_are_as_they_were)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for node, reason in _POSITION_PINS.items():
            if item.nodeid.endswith(node):
                item.add_marker(pytest.mark.xfail(reason=reason, strict=True))


@pytest.fixture
def make_df():
    import daft_tpu

    def _make(data):
        return daft_tpu.from_pydict(data)

    return _make
