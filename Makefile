# Developer/CI entry points. `make lint` is the static gate CI runs
# alongside the tier-1 pytest suite (ROADMAP.md); see docs/lint.md.

PY ?= python

.PHONY: lint lint-deep test check

lint:
	$(PY) -m pio_tpu.tools.cli lint pio_tpu/ tests/ eval/ examples/
	$(PY) -m compileall -q pio_tpu tests eval examples

# whole-program tier (docs/lint.md "Deep analysis"): lock-order cycles,
# blocking-under-lock, context-loss, route-contract drift. Fails on any
# finding not in pio_tpu/analysis/deep_baseline.json and on blowing the
# 30s wall-clock budget.
lint-deep:
	$(PY) -m pio_tpu.tools.cli lint --deep --max-seconds 30 pio_tpu/

# tier-1 verify (ROADMAP.md): CPU-only, not-slow subset
test:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

check: lint lint-deep test
