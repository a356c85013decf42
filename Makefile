# Build / test / vector orchestration.
# Capability parity with /root/reference Makefile:43-104 (pyspec build, tests,
# lint, YAML vector generation, deposit-contract tests) — compiled-spec steps
# don't exist here (the spec IS the package), so targets map to the runtime
# equivalents.

PYTHON ?= python
VECTOR_DIR ?= out/vectors
JUNIT ?= out/test-results.xml

.PHONY: test testall citest citest-cov citest-mainnet lint analyze contracts ranges lifetime memory vectors vectors-minimal chip-smoke multichip telemetry chaos firehose smoke clean

# measured 90.64% on the round-5 full suite; floor set just under so real
# regressions fail while normal drift doesn't
COV_FLOOR ?= 88

# Default lane: the suite minus the `slow`-marked modules (pairing corpus,
# state-to-state) — sub-10-minute on the virtual CPU mesh.
test:
	$(PYTHON) -m pytest tests/ -q -m "not slow"

# Everything, including slow.
testall:
	$(PYTHON) -m pytest tests/ -q

# CI flavor: full suite, fail fast, machine-readable results.
citest:
	mkdir -p $(dir $(JUNIT))
	$(PYTHON) -m pytest tests/ -x -q --junitxml=$(JUNIT)

# CI coverage gate (the reference Makefile:49-58 runs
# --cov): full suite under the stdlib line tracer (tools/cov.py), then
# fail below the floor. Artifact: out/coverage.json.
citest-cov:
	mkdir -p $(dir $(JUNIT))
	CSTPU_COV=1 $(PYTHON) -m pytest tests/ -x -q --junitxml=$(JUNIT)
	$(PYTHON) tools/cov.py --check --floor $(COV_FLOOR)

# Preset-divergence gate: the corpus subset where mainnet differs most from
# minimal (committee counts 64 vs 8, 90 vs 10 shuffle rounds, 64-slot
# epochs) runs under CSTPU_PRESET=mainnet.
citest-mainnet:
	CSTPU_PRESET=mainnet CSTPU_ACCEL=1 $(PYTHON) -m pytest \
		tests/test_spec_phase0.py -x -q \
		-k "attestation or crosslinks or registry_updates or sanity_slots or finality"

# Syntax + style gate (see tools/lint.py; no third-party linters in image).
lint:
	$(PYTHON) tools/lint.py consensus_specs_tpu tests chip_smoke.py __graft_entry__.py tools

# Trace-safety / spec-conformance static analysis (tools/analysis/README.md):
# ten pass families over the call-graph IR — Python control flow on
# tracers, 32-bit truncation of uint64 math, impure traced code,
# state-aliasing overrides, jit-cache hygiene, sharding/collective axis
# consistency, pallas BlockSpec/grid/Ref contracts, spec drift vs the
# reference pyspec (REFERENCE_ROOT, skips with a notice when absent),
# wide-column accumulation past the double-width laziness budget (CSA901),
# and unfenced perf_counter timing around jitted dispatch (CSA1001).
# Exit 0 = no findings beyond the committed baseline + inline
# `# csa: ignore[...]` suppressions. JSON artifact: out/analysis.json.
REFERENCE_ROOT ?= /root/reference
analyze:
	$(PYTHON) -m tools.analysis consensus_specs_tpu chip_smoke.py __graft_entry__.py \
		--baseline tools/analysis/baseline.json --json out/analysis.json \
		--reference-root $(REFERENCE_ROOT)

# Trace-tier contract analyzer (tools/analysis/trace/): traces/lowers the
# REAL jitted kernels named by the modules' TRACE_CONTRACTS and ratchets
# measured op budgets (REDC lanes, dependent add chains, pair-hash lanes,
# collective inventory, chained out/in shardings, donation survival, f64/
# callback/transfer hygiene) against the committed
# tools/analysis/trace_baseline.json. Pins XLA:CPU with 8 virtual devices
# itself, so it runs identically on CI and laptops. Exit 0 = every budget
# met. JSON artifact: out/contracts.json. Tighten a budget by editing the
# contract next to its kernel; loosen one via --update-trace-baseline.
contracts:
	mkdir -p out
	JAX_PLATFORMS=cpu $(PYTHON) -m tools.analysis --trace \
		--trace-baseline tools/analysis/trace_baseline.json \
		--json out/contracts.json

# Value-range tier (tools/analysis/ranges/): an interval abstract
# interpreter over the REAL jaxprs of the kernels' RANGE_CONTRACTS —
# proves the limb/column magnitude budgets (|col| < 2^35 into fq_redc,
# narrow limbs back to [-16, 2^29], shuffle int32 at the 2^30 ceiling,
# uint64 Gwei math at 10M validators) and the declared wrap semantics
# (SHA-256's mod-2^32), ratcheting the proven intervals against the
# committed tools/analysis/ranges_baseline.json (CSA1401-1404). Ceiling
# shapes trace via ShapeDtypeStruct, so the whole run is ~15 s of pure
# interpretation — no arrays, no devices. Exit 0 = every budget proven.
# JSON artifact: out/ranges.json. Loosen via --update-ranges-baseline.
ranges:
	mkdir -p out
	JAX_PLATFORMS=cpu $(PYTHON) -m tools.analysis --ranges \
		--ranges-baseline tools/analysis/ranges_baseline.json \
		--json out/ranges.json

# Buffer-lifetime tier (tools/analysis/lifetime/): the interprocedural
# donation/aliasing prover (CSA1501-1505) — abstract LIVE / DONATED /
# MAYBE-DONATED ownership states flow over the call-graph IR through
# calls, dispatch wrappers, attribute stores, destructuring and loops,
# cross-checked against the `tf.aliasing_output` annotations that
# survive the REAL lowerings of the donate_min trace contracts. Exit
# 0 = the committed tree proves clean (every donated buffer rebound,
# returned, or routed through utils/donation.platform_donated_jit).
# JSON artifact: out/lifetime.json. Accepted findings ratchet via
# tools/analysis/lifetime_baseline.json (--update-lifetime-baseline).
lifetime:
	mkdir -p out
	JAX_PLATFORMS=cpu $(PYTHON) -m tools.analysis --lifetime \
		--lifetime-baseline tools/analysis/lifetime_baseline.json \
		--json out/lifetime.json

# Memory tier (tools/analysis/memory/): a peak-buffer-liveness abstract
# interpreter over the REAL jaxprs of the kernels' MEM_CONTRACTS at
# ceiling shapes (V=10M epoch, 1M-leaf forest, G=128 pairing, firehose
# steady state) — donation-aware per-eqn live sets prove each kernel's
# declared HBM budget (CSA1601), per-shard bytes on the 8-device mesh,
# scaling exponents from probe shapes (CSA1603), and the Pallas VMEM
# footprint vs the 16 MiB core (CSA1604), cross-checked against
# compiled.memory_analysis() where XLA reports it and ratcheted against
# the committed tools/analysis/memory_baseline.json (CSA1602). Traces
# via ShapeDtypeStruct — no ceiling-sized arrays are ever allocated.
# Exit 0 = every budget proven. JSON artifact: out/memory.json. Loosen
# via --update-memory-baseline.
memory:
	mkdir -p out
	JAX_PLATFORMS=cpu $(PYTHON) -m tools.analysis --memory \
		--memory-baseline tools/analysis/memory_baseline.json \
		--json out/memory.json

# Conformance vectors, both presets (reference: make gen_yaml_tests).
vectors:
	$(PYTHON) -m consensus_specs_tpu.generators -o $(VECTOR_DIR)

vectors-minimal:
	$(PYTHON) -m consensus_specs_tpu.generators -o $(VECTOR_DIR) -p minimal

# What no benchmark cell drives, on one TPU chip: the device check and the
# Mosaic pair-hash kernel (`--bls`: one block through the jax BLS backend).
# Exits non-zero where jax finds no TPU. The served path's bring-up is
# `python3 benchmark/run.py --workload mainnet-1m.replay --seed 1
# --seconds 51 --trace 0`.
chip-smoke:
	$(PYTHON) chip_smoke.py

# The driver's multi-chip dry run, locally on 8 virtual devices.
multichip:
	$(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# Observability smoke: the resident serving loop with telemetry on —
# dumps out/trace.json (Chrome trace), out/metrics.prom (Prometheus
# exposition), out/telemetry.jsonl, and fails if the retrace/re-layout
# watchdogs record any event on the steady-state drive (CI artifacts).
telemetry:
	$(PYTHON) tools/telemetry_smoke.py

# Chaos drill (tools/chaos_drill.py): the resident serving loop driven
# through a seeded fault schedule — transient raises, a poisoned output
# (tripwired against the proven RANGE_CONTRACTS hulls), a hang past the
# armed deadline, a full degradation-ladder walk down to single-device,
# a corrupt checkpoint generation, and a kill mid-write — asserting the
# final state is BIT-IDENTICAL to the fault-free run with zero residual
# watchdog events. Artifact: out/chaos.json (CI uploads it).
chaos:
	$(PYTHON) tools/chaos_drill.py

# Firehose smoke (tools/firehose_smoke.py): the streaming verifier under
# sustained synthetic gossip load — waves of valid + deterministic-FALSE
# aggregates accumulated across slot ticks into full device batches,
# flushed at an armed deadline. Exits non-zero on any streamed-vs-
# synchronous verdict mismatch, watchdog event, or deadline miss.
# Artifact: out/firehose.json (CI uploads it). The smoke shape defaults
# to 8 groups for speed (CSTPU_FIREHOSE_GROUPS overrides).
firehose:
	$(PYTHON) tools/firehose_smoke.py

# Quick health check: lint + static analysis (all five tiers) + the
# fast test modules. `make contracts`, `make ranges`, `make lifetime`
# and `make memory` ride here so an op-budget, value-range,
# buffer-lifetime or memory-budget regression fails at smoke time,
# before any benchmark run.
smoke:
	$(PYTHON) tools/lint.py consensus_specs_tpu tests chip_smoke.py __graft_entry__.py tools
	$(PYTHON) -m tools.analysis --list-rules >/dev/null
	$(PYTHON) -m tools.analysis consensus_specs_tpu chip_smoke.py __graft_entry__.py \
		--baseline tools/analysis/baseline.json \
		--reference-root $(REFERENCE_ROOT)
	$(MAKE) contracts
	$(MAKE) ranges
	$(MAKE) lifetime
	$(MAKE) memory
	$(MAKE) firehose
	$(PYTHON) -m pytest tests/test_config.py tests/test_ssz.py tests/test_fork_choice.py tests/test_sharding.py tests/test_incremental_merkle.py tests/test_scalar_mul.py tests/test_fq_redc.py tests/test_analysis.py tests/test_trace_contracts.py tests/test_range_contracts.py tests/test_lifetime.py tests/test_memory_contracts.py tests/test_chip_smoke.py tests/test_multichip.py tests/test_resident.py tests/test_telemetry.py tests/test_resilience.py tests/test_chaos_checkpoint.py tests/test_streaming.py -q -m "not slow"

clean:
	rm -rf out .pytest_cache $(VECTOR_DIR)
	find . -name __pycache__ -type d -exec rm -rf {} +
