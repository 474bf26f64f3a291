# Verification entry points. `make verify` = tier-1 tests + serving benchmark.
#
# CI splits the gate in four (see .github/workflows/ci.yml):
#   verify-core-tests — everything except the serving-regression suite and
#                       the kernel/pool suite (each has its own job);
#   verify-kernels    — TailPool/DeviceTailPool equivalence + the ragged
#                       decode_attention kernel sweep (fast inner loop);
#   verify-serving-tests — parity + property + golden tests (the serving
#                       benchmark with its decode/mixed gates runs once in
#                       CI, inside bench-trend; local `verify-serving`
#                       still runs both), plus verify-hybrid (the
#                       compute-or-load hybrid re-prefill suite),
#                       verify-disagg (prefill/decode disaggregation:
#                       topology, KV handoff, real-mode bit-parity) and
#                       verify-store (three-tier content-addressed prefix
#                       store + cache property invariants) in the same
#                       serving-regression job;
#   bench-trend       — the serving throughput benchmark (all of its
#                       acceptance asserts) + its JSON vs the committed
#                       baseline (benchmarks/check_trend.py regression
#                       gate).

PY := python
export PYTHONPATH := src

# test_serving_parity.py / test_mixed_batch_props.py include the real-mode
# (wall-clock, interpret-Pallas) regression tests: the c=1 bit-parity matrix
# vs drive_serial, the real batch-former properties and the real
# preempt->resume round trip
SERVING_TESTS := tests/test_serving.py tests/test_serving_parity.py \
	tests/test_channelsim_props.py tests/test_mixed_batch_props.py \
	tests/test_golden_trace.py tests/test_decode.py

# compute-or-load hybrid re-prefill: planner properties, force-load/no-planner
# bit-identity for all four engines, real-mode recomputed-KV-vs-store
# exactness and the vmapped prefill-chunk batch former (runs in the
# serving-regression CI job via verify-hybrid; ignored by verify-core-tests)
HYBRID_TESTS := tests/test_hybrid.py

# prefill/decode disaggregation: DisaggTopology parsing, sim KV-handoff +
# worker routing, the worker-ratio sweep property, and the real-mode
# pool-handoff bit-parity matrix (runs in the serving-regression CI job via
# verify-disagg; ignored by verify-core-tests)
DISAGG_TESTS := tests/test_disagg.py

# the verify-kernels suite (its own CI job; ignored by verify-core-tests so
# nothing runs twice): TailPool/DeviceTailPool equivalence tests, the
# device-pool no-reupload/swap tests, the decode_attention ragged-batch
# kernel sweep, and the AOT compiles of the kernels for a described v5e
KERNEL_TESTS := tests/test_kernels.py tests/test_tail_pool.py \
	tests/test_device_pool.py tests/test_tpu_compile.py

# three-tier content-addressed prefix store: segment-log layout/compaction,
# the HBM->DRAM->SSD demotion cascade, digest refcounts/dedup and the
# cross-policy cache property invariants (runs in the serving-regression CI
# job via verify-store; ignored by verify-core-tests)
STORE_TESTS := tests/test_tierstore.py tests/test_cache_props.py

# heterogeneous fleet serving: family-aware step plans (SSM StatePool decode,
# MoE active-expert weight pricing), mixed-fleet batch purity properties and
# the per-family c=1 real-mode bit-parity matrix (runs in the
# serving-regression CI job via verify-fleet; ignored by verify-core-tests);
# the SelectiveScan kernel suite rides along as the SSM decode inner loop
FLEET_TESTS := tests/test_fleet.py

# config-zoo smoke matrix (its own CI job via verify-zoo; ignored by
# verify-core-tests): every config in src/repro/configs/ builds a step plan
# and survives a sim decode, frontend archs via their embeds path
ZOO_TESTS := tests/test_zoo.py

# multi-device serving: data-parallel replicas behind one Scheduler, the
# tensor-parallel paged decode attend (8-virtual-device parity vs the
# single-device oracle), the serving mesh factory, and the sharded sparse
# decode sweep — runs under forced host devices via verify-sharded (its own
# CI job; ignored by verify-core-tests)
SHARDED_TESTS := tests/test_sharded_sparse.py tests/test_sharding_small.py \
	tests/test_sharded_decode.py tests/test_replicas.py

.PHONY: verify verify-core verify-core-tests verify-kernels verify-serving \
	verify-serving-tests verify-hybrid verify-disagg verify-store \
	verify-fleet verify-zoo verify-sharded test bench-throughput \
	bench-baseline bench-trend

verify: test bench-throughput

test:
	$(PY) -m pytest -x -q

verify-core: verify-core-tests verify-kernels verify-serving

# full-tree discovery minus the suites owned by the other jobs
verify-core-tests:
	$(PY) -m pytest -q --durations=15 \
		$(addprefix --ignore=,$(SERVING_TESTS)) \
		$(addprefix --ignore=,$(KERNEL_TESTS)) \
		$(addprefix --ignore=,$(HYBRID_TESTS)) \
		$(addprefix --ignore=,$(DISAGG_TESTS)) \
		$(addprefix --ignore=,$(STORE_TESTS)) \
		$(addprefix --ignore=,$(FLEET_TESTS)) \
		$(addprefix --ignore=,$(ZOO_TESTS)) \
		$(addprefix --ignore=,$(SHARDED_TESTS))

# fast inner loop for kernel / TailPool / DeviceTailPool work
verify-kernels:
	$(PY) -m pytest -q --durations=15 $(KERNEL_TESTS)

verify-serving-tests:
	$(PY) -m pytest -q --durations=15 $(SERVING_TESTS)

verify-hybrid:
	$(PY) -m pytest -q --durations=15 $(HYBRID_TESTS)

verify-disagg:
	$(PY) -m pytest -q --durations=15 $(DISAGG_TESTS)

verify-store:
	$(PY) -m pytest -q --durations=15 $(STORE_TESTS)

# heterogeneous fleet lane: mixed-fleet suite + the selective_scan kernel
# trio that backs real-mode SSM decode
verify-fleet:
	$(PY) -m pytest -q --durations=15 $(FLEET_TESTS)
	$(PY) -m pytest -q tests/test_kernels.py -k SelectiveScan

# config-zoo smoke matrix: step plan + sim decode for every registry config
verify-zoo:
	$(PY) -m pytest -q --durations=15 $(ZOO_TESTS)

# multi-device lane: 8 forced host devices so the TP parity test, the
# replica suite and the sharded sparse sweep all see a real mesh
verify-sharded:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest -q --durations=15 $(SHARDED_TESTS)

verify-serving: verify-serving-tests verify-hybrid verify-disagg verify-store \
		verify-fleet
	$(PY) benchmarks/bench_throughput.py --quick

bench-throughput:
	$(PY) benchmarks/bench_throughput.py --quick

# refresh the committed benchmark baseline after an intentional perf change
bench-baseline:
	$(PY) benchmarks/bench_throughput.py --quick --json benchmarks/baseline.json

# what the bench-trend CI job runs: fresh JSON + regression gate vs baseline
bench-trend:
	$(PY) benchmarks/bench_throughput.py --quick --json benchmarks/out/bench_ci.json
	$(PY) benchmarks/check_trend.py benchmarks/out/bench_ci.json
