# Dev entry points (the justfile-equivalent). `make help` lists targets.

PY ?= python

.PHONY: help test test-all speclint speclint-json speclint-sarif speclint-changed speclint-all forkdiff chip-smoke bench-smoke chaos mesh-smoke mem-smoke pool-smoke proofs-smoke soak-smoke trace-smoke pipeline-selfcheck trace metrics profile serve serve-data server-smoke serving-smoke

PROFILE_DIR ?= profile_artifacts

help:  ## list targets
	@grep -E '^[a-z][a-zA-Z_-]*:.*##' $(MAKEFILE_LIST) | awk -F':.*## ' '{printf "  %-20s %s\n", $$1, $$2}'

test:  ## tier-1 suite (hermetic CPU, slow tests deselected)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

test-all:  ## full suite including slow bench-shaped tests
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q

SPECLINT_REPORT ?= speclint_report.json

speclint:  ## whole-package static analysis: fork drift, SSZ purity, concurrency, device discipline, silent fallbacks, observability contract, env flags (JSON artifact left behind on failure)
	@$(PY) -m tools.speclint --report $(SPECLINT_REPORT) && rm -f $(SPECLINT_REPORT) || { echo "findings report: $(SPECLINT_REPORT)"; exit 1; }

speclint-json:  ## same, JSON report on stdout
	$(PY) -m tools.speclint --format json

speclint-sarif:  ## same, SARIF 2.1.0 on stdout (code-scanning UIs)
	$(PY) -m tools.speclint --format sarif

speclint-changed:  ## lint only the git working set (tracked diffs + untracked)
	$(PY) -m tools.speclint --changed

speclint-all:  ## include allowlisted findings in the listing
	$(PY) -m tools.speclint --all

forkdiff:  ## regenerate docs/FORKDIFF.md from the fork-diff machinery
	$(PY) -m tools.speclint --write-forkdiff

chip-smoke:  ## the main path end to end on ONE TPU chip at the 2^20-validator deneb deployment (chip_smoke.py; on a four-chip host `$(PY) chip_smoke.py --chips 4` runs the mesh path instead); fails without a TPU
	$(PY) chip_smoke.py

bench-smoke:  ## tier-1-adjacent: one warm 2^14 deneb block (columnar engine engaged) + a 2^18 columnar-primary epoch engagement check + the 2^18 phase0 committee-mask engagement check + the scenario smoke + the serving smoke + the pool smoke + the mesh smoke + the soak smoke + the memory-observatory smoke + the proof-plane smoke + the trace-plane smoke
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_ops_vector.py tests/test_epoch_vector.py tests/test_committee_masks.py tests/test_scenarios.py tests/test_serving.py tests/test_pool.py tests/test_mesh_runtime.py tests/test_soak.py tests/test_memory_observatory.py tests/test_proofs.py tests/test_trace_plane.py -q -m 'bench_smoke or chaos_smoke or serving_smoke or pool_smoke or mesh_smoke or soak_smoke or mem_smoke or proofs_smoke or trace_smoke'
	$(PY) -m tools.speclint --changed

mesh-smoke:  ## 2-device virtual mesh: one sharded epoch pass + one sharded RLC flush window, bit-identical to host
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_mesh_runtime.py -q -m mesh_smoke

mem-smoke:  ## memory observatory: one 2^14 epoch under the observatory — phase ledger bracketing, >=3 census owners, bandwidth at bulk_store, profile ceiling asserted
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_memory_observatory.py -q -m mem_smoke

proofs-smoke:  ## proof plane: one warm walk — branches + a multiproof byte-identical to the cold prove walk, zero declines/fallbacks
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_proofs.py -q -m proofs_smoke

chaos:  ## fast scenario smoke: one short invalid-block storm + one fork-boundary chain (minutes)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_scenarios.py -q -m chaos_smoke

pool-smoke:  ## operation-pool write plane: client round-trips, block publication, attester-slashing storm
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pool.py -q -m pool_smoke

soak-smoke:  ## short deterministic production soak: storm + faults + readers + SSE + pool traffic, all three gates asserted
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_soak.py -q -m soak_smoke

trace-smoke:  ## causal trace plane: one end-to-end linked trace on a 2-lane pipelined replay, zero dropped spans
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_trace_plane.py -q -m trace_smoke

pipeline-selfcheck:  ## pipeline smoke: seq-vs-pipelined bit identity
	JAX_PLATFORMS=cpu $(PY) -m ethereum_consensus_tpu.pipeline --selfcheck

trace:  ## record a pipeline run as Chrome trace JSON (open in Perfetto)
	JAX_PLATFORMS=cpu $(PY) -m ethereum_consensus_tpu.pipeline --selfcheck --trace-out trace.json
	@echo "load trace.json at https://ui.perfetto.dev or chrome://tracing"

metrics:  ## dump the telemetry metrics registry after a pipeline run
	JAX_PLATFORMS=cpu $(PY) -m ethereum_consensus_tpu.pipeline --selfcheck --metrics-out metrics.json
	@cat metrics.json

profile:  ## one-command capture artifact: selfcheck with Chrome trace + metrics snapshot + device ledger in $(PROFILE_DIR)/ (CPU backend; the chip run is `make chip-smoke`)
	mkdir -p $(PROFILE_DIR)
	JAX_PLATFORMS=cpu $(PY) -m ethereum_consensus_tpu.pipeline --selfcheck --trace-out $(PROFILE_DIR)/trace.json --metrics-out $(PROFILE_DIR)/metrics.json --device-out $(PROFILE_DIR)/device.json
	@echo "capture artifact in $(PROFILE_DIR)/: trace.json (Perfetto), metrics.json, device.json"

serve:  ## pipeline selfcheck with the live introspection server up (held 30s: curl /metrics /healthz /blocks /events)
	JAX_PLATFORMS=cpu $(PY) -m ethereum_consensus_tpu.pipeline --selfcheck --serve 8799 --hold 30

serve-data:  ## selfcheck + the Beacon-API read data plane mounted (held 60s: curl /eth/v1/beacon/states/head/validators?id=0)
	JAX_PLATFORMS=cpu $(PY) -m ethereum_consensus_tpu.pipeline --selfcheck --serve 8799 --serve-data --hold 60

serving-smoke:  ## tier-1-adjacent: client<->server round-trip vs the scalar oracle on a short pipelined replay
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serving.py -q -m serving_smoke

server-smoke:  ## tier-1-adjacent: scrape /metrics + /blocks during a short pipelined replay
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_flight_server.py -q -m server_smoke
