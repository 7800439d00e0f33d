# Developer/CI entry points. The record of speed is PERF_LEDGER.jsonl, written by
# the driver from `python3 benchmark/run.py` on the chip (BENCHMARK.json, PERF.md).

PY ?= python

.PHONY: test lint lint-json test-ai test-fusion test-pallas test-mesh test-fault test-oom test-gateway calibrate-report doctor serve chip-smoke

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

# Machine-readable finding counts.
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
# kernels — int64 exactness past 2^53, null keys, fused-repartition
# zero-all_to_all assert, no-import guard.
# 8 forced host devices so the mesh/ring sections run off-silicon.
test-pallas:
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest tests/test_pallas_join.py tests/test_fused_region.py \
		-q -p no:cacheprovider

# In-mesh SPMD suite under 8 forced host devices: the sharded stages and the
# sharded join dispatch against one chip and the host, sharded residency, the
# mesh arm of the placement decisions, what the sharded dispatch declines.
test-mesh:
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest tests/test_mesh_*.py tests/test_device_join_mesh.py \
		tests/test_distributed.py \
		-q -p no:cacheprovider

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

# Flight-recorder triage (daft_tpu/tools/doctor.py): rank what each anomaly
# dump shows (errors, worker deaths, stalls, ledger pressure, placements).
#   make doctor DUMPS="dump1.json dump2.json"
doctor:
	@test -n "$(DUMPS)" || (echo 'usage: make doctor DUMPS="dump1.json ..."' && exit 2)
	$(PY) -m daft_tpu.tools.doctor $(DUMPS)

# Cost-model calibration report (daft_tpu/tools/calibrate.py): run a forced
# priced probe workload, replay the placement ledger's observed-vs-predicted
# samples, and print suggested DAFT_TPU_COST_* overrides. On real silicon,
# run WITHOUT JAX_PLATFORMS=cpu so the link terms are measured on the device.
calibrate-report:
	env JAX_PLATFORMS=$${JAX_PLATFORMS:-cpu} $(PY) -m daft_tpu.tools.calibrate
