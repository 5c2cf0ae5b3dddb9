PYTHON ?= python

.PHONY: lint lint-concurrency test ruff metrics-check swarm fleet \
	device-runtime-smoke snapshot-smoke archive-smoke alert-smoke

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
# stitched push_tx trace printed.  Exit 1 if any core assertion came
# back false (the watchtower's zero-alerts-on-a-clean-run among them),
# the stitched trace crossed fewer than three nodes, or the two
# fingerprints differ.
fleet:
	JAX_PLATFORMS=cpu $(PYTHON) -m upow_tpu.fleet --check-determinism \
		--trace --out fleet.json

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
