PYTHON ?= python

.PHONY: lint lint-concurrency test ruff metrics-check perf-observatory \
	perf-smoke swarm fleet device-runtime-smoke snapshot-smoke \
	archive-smoke alert-smoke

# Domain linter: consensus-endianness, consensus-purity, jit-purity,
# dtype-hygiene, async-safety, broad-except, device-runtime purity.
# Stdlib-only; exits 1 on any unsuppressed error.
lint:
	$(PYTHON) -m upow_tpu.lint upow_tpu/
	@$(MAKE) --no-print-directory ruff

# Interprocedural concurrency sweep only (docs/STATIC_ANALYSIS.md, RC
# family): project-wide call graph + loop/thread coloring; RC001-RC005.
lint-concurrency:
	$(PYTHON) -m upow_tpu.lint --select RC upow_tpu/

# Generic baseline (ruff.toml); skipped quietly where ruff is not
# installed — the container bakes no ruff and we don't pip install.
ruff:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check upow_tpu/; \
	else \
		echo "ruff not installed; skipping generic baseline"; \
	fi

test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Boots an in-process node and validates its /metrics end to end:
# content type, exposition grammar, cumulative-bucket invariants, and
# the required kernel/chain metric families (docs/OBSERVABILITY.md).
metrics-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.telemetry.selfcheck

# Full perf observatory: wallet-population load against the in-process
# node + kernel benches, merged into observatory.json with provenance,
# one trajectory line appended to PROGRESS.jsonl.  Gate the artifact
# against a baseline with:
#   $(PYTHON) -m upow_tpu.loadgen.gate --against <an earlier observatory.json>
perf-observatory:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.loadgen \
		--out observatory.json --progress PROGRESS.jsonl

# Deterministic multi-node scenario matrix (docs/SWARM.md): partition/
# heal, reorg storm, eclipse, spam, DPoS governance, WS churn — all
# in-process, seeded, a few seconds total.  Exit 1 if any core
# assertion in any scenario came back false.
swarm:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.swarm --matrix fast \
		--out swarm.json

# Fleet observatory (docs/OBSERVABILITY.md "Fleet observatory"): the
# deterministic geo-soak run twice (same seed must reproduce the core
# fingerprint byte-identically), propagation percentiles and the
# stitched push_tx trace printed, then the fleet kernel rows gated
# against the committed observatory baseline (fleet_core_ok enforced;
# it zeroes on any core assertion failure, defeating any tolerance).
fleet:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.fleet --check-determinism \
		--trace --out fleet.json --gate-against observatory.json

# CI-sized variant: tiny population, no PROGRESS append.  Gates
# (report-only) against the committed artifact so every metric —
# including verify_pipeline, the readpath cache scenario, and the
# config-14 coresidency scenario with their explicit direction
# metadata — is registered with gate.py on each smoke run.  The
# readpath and coresidency headlines zero themselves (tripping the
# gate) if their byte differentials ever diverge.
# Report-only overall, but the verify-pipeline, resident-accept and
# mesh-mining kernels are ENFORCED (ISSUES 11, 12): a differential
# divergence zeroes those headline values, so the enforced gate also
# catches correctness breaks, not just slowdowns.  Per-metric
# tolerances are wider than the global band because smoke-sized runs
# on shared CI hosts are noisy.  mine_mesh_speedup is a ratio of two
# short measurements (widest band); its correctness trip is the
# differential zeroing, which defeats any tolerance.
# fleet_core_ok (ISSUE 13) is ENFORCED the same way: the geo-soak
# zeroes it on any failed core assertion, so the gate trips on broken
# distribution semantics; the propagation quantiles are wall-clock
# under load (widest bands) and report-only by substring.
# archive_parity_ok (ISSUE 19) is ENFORCED identically: the pruned-vs-
# twin scenario zeroes it when any archived read diverges from the
# unpruned twin, so the gate trips on a broken hot/archive seam.
# watchtower_clean_ok (ISSUE 20) is ENFORCED the same way: the geo-soak
# runs with the default alert rule pack armed on every node and zeroes
# the kernel if any alert fires on the clean run (or the engine never
# ticked), so a rule pack that pages on healthy churn fails the gate.
perf-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.loadgen --smoke \
		--out observatory-smoke.json \
		--against observatory.json --report-only \
		--enforce kernel.verify_pipeline \
		--enforce kernel.accept_ \
		--enforce kernel.mine_mesh \
		--enforce kernel.fleet_core_ok \
		--enforce kernel.archive_parity_ok \
		--enforce kernel.watchtower_clean_ok \
		--metric-tolerance kernel.verify_pipeline=0.60 \
		--metric-tolerance kernel.verify_pipeline_serial=0.60 \
		--metric-tolerance kernel.verify_pipeline_speedup=0.60 \
		--metric-tolerance kernel.accept_resident=0.60 \
		--metric-tolerance kernel.accept_serial=0.60 \
		--metric-tolerance kernel.accept_scan_speedup=0.60 \
		--metric-tolerance kernel.mine_mesh_sharded=0.60 \
		--metric-tolerance kernel.mine_mesh_serial=0.60 \
		--metric-tolerance kernel.mine_mesh_speedup=0.45 \
		--metric-tolerance kernel.fleet_block_prop_p50_ms=3.0 \
		--metric-tolerance kernel.fleet_block_prop_p95_ms=3.0 \
		--metric-tolerance kernel.fleet_tx_prop_p50_ms=3.0 \
		--metric-tolerance kernel.fleet_tx_prop_p95_ms=3.0

# Snapshot sync gate (docs/SNAPSHOT.md): a build→serve→restore
# round-trip on a two-node loopback swarm (byte-exact fingerprints,
# generation rotation), then the snapshot_churn scenario — corruption,
# mid-transfer partition, journaled failover resume, replay fallback —
# run twice so the core fingerprint must reproduce byte-identically.
snapshot-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.snapshot --check-determinism

# Archive tier gate (docs/ARCHIVE.md): a multi-thousand-block
# pruned-vs-twin deep-read differential, a kill -9 between
# archive-commit and hot-delete that must resume losslessly, and the
# archive_prune scenario (HTTP parity incl. a reorg inside the safety
# window, peer mirror) run twice so the core fingerprint must
# reproduce byte-identically.
archive-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.archive --check-determinism

# Alerting gate (docs/ALERTING.md): jax-free detector and burn-rate
# golden units, the alert state machine, then the watchtower_storm
# scenario — injected gossip faults must page breaker_flip_storm with
# a cross-node exemplar and the flight recorder must dump with the
# alert as the trigger — run twice so the core fingerprint must
# reproduce byte-identically.
alert-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.watchtower --check-determinism

# Device-runtime gate (docs/DEVICE_RUNTIME.md): the fairness /
# coalescing / degrade-flip / arm-failure test matrix, then the DR
# lint family proving no dispatch path bypasses the runtime.
device-runtime-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_device_runtime.py -q \
		-p no:cacheprovider
	$(PYTHON) -m upow_tpu.lint upow_tpu/ --select DR001,DR002,DR003
