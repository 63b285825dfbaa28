# Developer entry points (reference parity: Makefile:1-15 exposes
# docker build/run-test; here the runtime is local JAX + the native
# C++ components, built on demand by tests).

PYTHON ?= python

.PHONY: install test test-fast test-pyspark native cluster-up clean \
	lint lint-obs

install:
	$(PYTHON) -m pip install -e .

# sparklint: the AST-based static-analysis pass (sparktorch_tpu/lint/).
# It replaced this Makefile's six grep stanzas — the rules are now
# scope-aware (with-blocks, call structure, import aliases) and each
# encodes a bug class this repo actually shipped: lock-held percentile
# roll-ups (PR 9/11), raw clocks outside wall_ts/LedgerSpans (PR 13),
# the Telemetry.event(kind=...) envelope collision, jit retrace
# hazards (PR 14), collectives outside shard_map scope (PR 12), and
# stopped-handle use-after-free (PR 10). Rule table + suppression
# syntax (`# lint-obs: ok (<why>)`): README "Static analysis";
# `python -m sparktorch_tpu.lint --list-rules` for the live list.
lint:
	@$(PYTHON) -m sparktorch_tpu.lint sparktorch_tpu/

# Back-compat alias: `make lint-obs` keeps working (the historical
# target name the grep stanzas lived under).
lint-obs: lint

test: lint
	$(PYTHON) -m pytest tests/ -q

test-fast: lint
	$(PYTHON) -m pytest tests/ -q -m "not slow"

# Real pyspark + JVM persistence harness (skips without pyspark/java;
# `pip install -e .[spark]` + a JRE make it run for real). Own process
# so the localspark shim never shadows genuine pyspark.
test-pyspark:
	$(PYTHON) -m pytest tests/test_real_pyspark.py -v

# Genuine Spark standalone cluster (master+worker+driver) running the
# adapter example and the JVM persistence tests. Reference parity:
# docker-compose.yml:3-25.
cluster-up:
	docker compose -f deploy/docker/docker-compose.yml up --build \
		--abort-on-container-exit --exit-code-from driver

# Build the native C++ runtime (gang coordinator, rowpack parser)
# explicitly; tests otherwise build it on first use.
native:
	$(PYTHON) -c "from sparktorch_tpu.native.build import load_library; \
	load_library('gang'); load_library('rowpack'); print('native OK')"

clean:
	rm -rf build dist *.egg-info sparktorch_tpu/native/_build
