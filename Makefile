# Repro CI lanes.  `make test` is tier-1; the kernel lane re-runs the
# dispatch-layer suites with the Pallas *interpreter* forced via
# REPRO_KERNEL_IMPL (the same override the TPU lane would set to
# `pallas`), so kernel==jnp bit-exactness is exercised even on hosts
# whose auto-selected engine is the jnp reference.
PY := PYTHONPATH=src python

.PHONY: test api-lane kernel-lane service-lane mesh-lane adversary-lane \
    chaos-lane obs-lane tune-lane funcs-lane bench-service \
    bench-service-mesh bench-stream bench-tune bench-funcs \
    bench

test:
	$(PY) -m pytest -x -q

# public-surface lane: the repro.api pins (snapshot __all__/signatures,
# ConfigError negatives, facade == engine bit-identity) plus a
# warnings-as-errors sweep over tier-1 proving nothing in-repo still
# touches a deprecated path (the mesh/slow subprocess cells have their
# own lane)
api-lane:
	$(PY) -m pytest tests/test_api.py -q
	PYTHONPATH=src python -W error::DeprecationWarning -m pytest -q \
	    -m "not mesh and not slow"

kernel-lane:
	REPRO_KERNEL_IMPL=pallas_interpret $(PY) -m pytest \
	    tests/test_secure_agg_kernels.py tests/test_service.py \
	    tests/test_engine.py -q

service-lane:
	$(PY) -m pytest tests/test_service.py tests/test_overlay.py \
	    tests/test_crypto.py -q

# distributed lane: MeshTransport == SimTransport bit-equivalence, the
# mesh half of the conformance grid, and the multi-device protocol paths
# (the tests spawn their own subprocesses with
# XLA_FLAGS=--xla_force_host_platform_device_count forced)
mesh-lane:
	$(PY) -m pytest tests/test_engine.py tests/test_distributed.py \
	    tests/test_conformance.py -q

# adversarial conformance grid (tests/adversary.py strategies over
# transport x masking) + vote/schedule property tests; the mesh cells
# belong to mesh-lane, so they are filtered out here
adversary-lane:
	$(PY) -m pytest tests/test_conformance.py tests/test_vote_schedules.py \
	    -m "not mesh" -q

# chaos-injected resilience conformance: retry/bisect/quarantine over
# every chaos mode x {sim, mesh}, deadlines, shedding, and the breaker
# degrade ladder, swept over the fixed chaos seeds baked into the
# suite's parametrizations (the storm tests replay seeds 0..2 exactly;
# the mesh cell forces 8 host devices in its own subprocess)
chaos-lane:
	$(PY) -m pytest tests/test_resilience.py tests/test_obs.py -m chaos -q

# observability lane: registry/recorder semantics, the stage-span and
# resilience event streams, and the wire-byte exactness chain
# (per-round trace events == Transport.bytes_sent == AggPlan.wire_bytes
# == schedule_cost); the chaos-marked byte-identical-replay test also
# runs under chaos-lane with the rest of the fixed-seed sweeps
obs-lane:
	$(PY) -m pytest tests/test_obs.py -q

# self-tuning planner lane: the golden decision table, the
# predicted==executed wire-byte pin, and the config-path bugfix
# regressions (XLA_FLAGS import purity, schedule ConfigError, the
# deprecated digest_ratio approximation) — run warnings-as-errors so
# the tuner can never score through the deprecated path
tune-lane:
	PYTHONPATH=src python -W error::DeprecationWarning -m pytest \
	    tests/test_tune.py -q

# secure-function layer lane: plan/pad arithmetic, every function
# pinned against the numpy oracle (engine, facade verbs, service
# sessions), the adversary-grid bit-identity, the cost == executed
# bytes chain, and the observed-churn tuner pins — warnings-as-errors
# like tune-lane, and the mesh subprocess cell rides along
funcs-lane:
	PYTHONPATH=src python -W error::DeprecationWarning -m pytest \
	    tests/test_funcs.py -q

bench-service:
	$(PY) -m benchmarks.run --only service --json BENCH_service.json

# distributed executor rows (service_executor_mesh_*) appended to the
# same trajectory file; forces one host device per protocol node.  The
# concurrency-optimized scheduler keeps 16 device threads from
# thrashing a core-starved CI host — same executable, same bits,
# ~1.4x on the collective rounds
MESH_XLA := --xla_force_host_platform_device_count=16 \
    --xla_cpu_enable_concurrency_optimized_scheduler=true

bench-service-mesh:
	XLA_FLAGS="$(MESH_XLA)" \
	    $(PY) -m benchmarks.run --only service --transport mesh \
	    --json BENCH_service.json

# streaming regression gate: re-runs the mesh service bench and fails
# if the pipelined executor's headline row regresses >10% vs the value
# committed in BENCH_service.json (the fresh value is still merged, so
# an intentional change is committed by rerunning after review)
bench-stream:
	XLA_FLAGS="$(MESH_XLA)" \
	    $(PY) -m benchmarks.run --only service --transport mesh \
	    --json BENCH_service.json \
	    --guard service_throughput_mesh_S64_sps

# tuner decision trajectory + resolution-overhead gate: the headline
# decision's predicted bytes may not regress (grow) >10% vs the value
# committed in BENCH_secure_agg.json, and a cache-hit resolution must
# stay within 2% of dispatching the winning config directly
bench-tune:
	$(PY) -m benchmarks.run --only tune --json BENCH_secure_agg.json \
	    --guard tuner_decision_n16_T1024_S8_bytes

# secure-function trajectory + wire gate: the median bisection's
# steps=1024 byte row may not grow >10% vs the committed value (the
# histogram==sum equality row rides in the same run)
bench-funcs:
	$(PY) -m benchmarks.run --only funcs --json BENCH_secure_agg.json \
	    --guard funcs_median_steps1024_bytes

bench:
	$(PY) -m benchmarks.run
