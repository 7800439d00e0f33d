# Developer/CI entry points. The perf gate compares a fresh bench capture
# against the newest committed BENCH_r*.json and fails loudly on >5% per-query
# regressions (bench.py --compare).

PY ?= python
LATEST_BENCH := $(shell ls BENCH_r*.json 2>/dev/null | sort -V | tail -1)
NEW_BENCH ?= /tmp/daft_tpu_bench_new.json

.PHONY: test lint lint-json test-ai test-fusion test-pallas test-mesh test-fault test-oom test-gateway bench bench-ai bench-fusion bench-pallas bench-mesh bench-serve bench-serve-net bench-oom bench-oom-quick bench-tpcds bench-gate bench-compare calibrate-report doctor serve chip-smoke

# `make test` includes the lint gate via tests/test_lint.py (tier-1).
test:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Engine-invariant linter (daft_tpu/tools/lint): lock discipline, env-knob
# discipline, counter pre-declaration, tier import discipline, broad-except
# audit, atomic publish, event-schema drift. Exits non-zero on any
# non-baselined finding.
lint:
	$(PY) -m daft_tpu.tools.lint

# Machine-readable finding counts (diff across PRs like bench.py captures).
lint-json:
	@$(PY) -m daft_tpu.tools.lint --json

# Elastic fault-tolerance suite: kill -9 / SIGSTOP real pool workers
# mid-query and assert recovery (detection, lost-map regeneration,
# respawn, checkpoint resume, serving cancellation). Recovery bugs tend to
# present as hangs, so the whole run gets a hard timeout; the process-level
# tests skip cleanly on platforms without POSIX kill/SIGSTOP semantics.
# GNU timeout is absent on stock macOS — fall back to an unbounded run there
# (the pytest-level skips still guard the POSIX-signal tests themselves)
TIMEOUT_CMD := $(shell command -v timeout >/dev/null 2>&1 && echo "timeout -k 10 600")
test-fault:
	$(TIMEOUT_CMD) env JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_fault_tolerance.py -q -p no:cacheprovider

# Device-UDF tier suite: device-vs-host bit-parity, coalesced dispatches,
# weight residency/pin safety, zero-overhead guard, plus the jax provider.
test-ai:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_device_udf.py \
		tests/test_jax_provider.py -q -p no:cacheprovider

# Whole-stage fusion suite (tier-1; also runs under `make test`): fused
# region 3-way bit-identity, mid-region fallback, Pallas interpret parity,
# zero-overhead guard.
test-fusion:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fused_region.py \
		-q -p no:cacheprovider

# Pallas kernel-tier suite (tier-1; also runs under `make test`): interpret-
# mode parity for the segment-reduce, hash-probe join, and ICI ring-permute
# kernels — int64 exactness past 2^53, null keys, lowering-failure fallback
# counters, fused-repartition zero-all_to_all assert, no-import guard.
# 8 forced host devices so the mesh/ring sections run off-silicon.
test-pallas:
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest tests/test_pallas_join.py tests/test_fused_region.py \
		-q -p no:cacheprovider

# Pallas kernel-tier capture (bench.py pallas_microbench): grouped aggs
# through the segment-reduce kernel, a star join-agg through the hash-probe
# kernel, a repartition through the in-kernel ICI ring permute (zero
# standalone all_to_all) — bit-checked vs the XLA tiers, derived
# pallas_dispatch_ratio in the JSON.
bench-pallas:
	env BENCH_PALLAS=1 JAX_PLATFORMS=cpu $(PY) bench.py

# Whole-stage fusion capture (bench.py fusion_microbench): an 8-morsel
# filter→project→UDF→agg chain, fused vs unfused dispatch counts,
# bit-identical results, derived fused_dispatch_ratio.
bench-fusion:
	env BENCH_FUSION=1 JAX_PLATFORMS=cpu $(PY) bench.py

# AI pipeline capture on the device-UDF tier (bench.py ai_bench): seeded
# encoder, embed + zero-shot classify + groupby count, bit-identical vs the
# host-UDF path, zero repeat weight re-upload, coalesced super-batches.
bench-ai:
	env BENCH_SUITE=ai JAX_PLATFORMS=cpu $(PY) bench.py

# In-mesh SPMD suite under 8 forced host devices (the MULTICHIP harness
# environment): bit-exact mesh vs single-chip vs host parity, sharded
# residency, cost-tier flips.
test-mesh:
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest tests/test_mesh_stage.py tests/test_mesh_join.py \
		tests/test_distributed.py \
		-q -p no:cacheprovider

# CPU-CI mesh capture: a TPC-H-shaped groupby sharded across 8 simulated
# devices, bit-identical across mesh/single-chip/host (bench.py mesh_microbench).
bench-mesh:
	env BENCH_MESH=1 JAX_PLATFORMS=cpu \
		XLA_FLAGS=--xla_force_host_platform_device_count=8 $(PY) bench.py

# Serving-tier capture: a 2-worker ServingSession replaying a mixed
# repeat-heavy stream from 4 concurrent clients on the CPU backend —
# p50/p99 + queries/sec, bit-identical vs serial, prepared hits > 0,
# hbm_h2d flat across repeats (bench.py serve_bench).
bench-serve:
	env BENCH_SERVE=1 JAX_PLATFORMS=cpu $(PY) bench.py

# Gateway capture: the same mixed stream replayed over the WIRE — an
# in-process gateway serving a multi-process client swarm (bench.py
# serve_bench_net): p50/p99/QPS, result-cache hit rate, warm-vs-uncached
# repeat latency, bit-identical vs in-process serial.
bench-serve-net:
	env BENCH_SERVE=1 BENCH_SERVE_NET=1 JAX_PLATFORMS=cpu $(PY) bench.py

# Wire-layer gateway suite: auth, framing, reconnect-resume, concurrent
# tenants, result-cache invalidation/eviction, QoS caps, kill -9 resume.
test-gateway:
	$(TIMEOUT_CMD) env JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_gateway.py -q -p no:cacheprovider

# Run the serving gateway standalone (the network front door). Override:
# make serve SERVE_ARGS="--port 8642 --demo-rows 200000". Runs on whatever
# device JAX finds; export JAX_PLATFORMS=cpu yourself for a host-only gateway.
SERVE_ARGS ?= --port 8642 --demo-rows 200000
serve:
	$(PY) -m daft_tpu.gateway $(SERVE_ARGS)

# The chip check: TPC-H through the main path on one TPU, then the real-TPU
# test tier, one process after the other (a chip belongs to one process at a
# time). Fails without a TPU. On the builder's tool:
#   chiprun --timeout 1500 -- make chip-smoke
# Override: make chip-smoke CHIP_SMOKE_ARGS="--sf 10". Four chips, mesh tier
# only: chiprun --chips 4 -- python chip_smoke.py --mesh 4
CHIP_SMOKE_ARGS ?=
chip-smoke:
	$(PY) chip_smoke.py $(CHIP_SMOKE_ARGS)
	$(PY) -m pytest tests_tpu -m tpu -q -p no:cacheprovider

# Out-of-core suite: host memory manager ledger/pressure semantics,
# streaming-scan split planning + backpressure, tiny-budget (~10% of input
# bytes) join/sort/agg bit-identity, spill-dir lifecycle/GC. Budget bugs
# tend to present as hangs (a stalled producer waiting on a ledger nobody
# drains), so the whole run gets a hard timeout.
test-oom:
	$(TIMEOUT_CMD) env JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_host_memory.py tests/test_streaming_scan.py \
		tests/test_oom_budget.py tests/test_out_of_core.py \
		-q -p no:cacheprovider

# Out-of-core capture: the TPC-H subset with lineitem through parquet
# streaming scans under DAFT_TPU_MEMORY_LIMIT pinned to a fraction of the
# dataset — bit-identical vs unbudgeted, spill counters + RSS high-water in
# the JSON. SF100-capable: BENCH_SF=100 make bench-oom on a big box.
bench-oom:
	env BENCH_OOM=1 JAX_PLATFORMS=cpu $(PY) bench.py

# Quick mode: the synthetic carry-preserving-merge microbench (no TPC-H
# datagen) — BENCH_OOM_ROWS rows forced through a multi-run external sort
# under a tiny budget, asserting bit-identity, the merge's O(rows)/level
# sort bound, and the prefetch high-water. The same body runs in tier-1
# via tests/test_spill_async.py.
BENCH_OOM_ROWS ?= 200000
bench-oom-quick:
	env BENCH_OOM=1 BENCH_OOM_ROWS=$(BENCH_OOM_ROWS) JAX_PLATFORMS=cpu \
		$(PY) bench.py

# TPC-DS store-sales capture (the star-join-heavy suite the mesh join tier
# targets): same one-JSON-line contract; pair with BENCH_MESH-style env on
# real silicon to record which join queries flip (bench.py --compare shows
# the per-query placement-flip column against a prior capture).
bench-tpcds:
	env BENCH_SUITE=tpcds $(PY) bench.py

bench:
	$(PY) bench.py

# CI perf gate: run the bench, diff against the latest committed capture.
bench-gate:
	@test -n "$(LATEST_BENCH)" || (echo "no BENCH_r*.json capture to gate against" && exit 2)
	$(PY) bench.py > $(NEW_BENCH)
	$(PY) bench.py --compare $(LATEST_BENCH) $(NEW_BENCH)

# Ad-hoc: make bench-compare OLD=BENCH_SF10_r05.json NEW=BENCH_SF10_r06.json
bench-compare:
	$(PY) bench.py --compare $(OLD) $(NEW)

# Regression-attribution triage (daft_tpu/tools/doctor.py): rank what got
# slower between two bench captures (per-operator/counter deltas when the
# captures carry per_query_profile, capture-level movement otherwise), or
# triage flight-recorder anomaly dumps: make doctor DUMPS="dump1.json ...".
# Defaults to the committed SF10 pair that bracketed the out-of-core tier.
DOCTOR_OLD ?= BENCH_SF10_r04.json
DOCTOR_NEW ?= BENCH_SF10_r05.json
doctor:
ifdef DUMPS
	$(PY) -m daft_tpu.tools.doctor $(DUMPS)
else
	$(PY) -m daft_tpu.tools.doctor --compare $(DOCTOR_OLD) $(DOCTOR_NEW)
endif

# Cost-model calibration report (daft_tpu/tools/calibrate.py): run a forced
# priced probe workload, replay the placement ledger's observed-vs-predicted
# samples, and print suggested DAFT_TPU_COST_* overrides. On real silicon,
# run WITHOUT JAX_PLATFORMS=cpu so the link terms are measured on the device.
calibrate-report:
	env JAX_PLATFORMS=$${JAX_PLATFORMS:-cpu} $(PY) -m daft_tpu.tools.calibrate
