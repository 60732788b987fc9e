# Convenience targets — the CI story in four words: make lint, make test.
PY ?= python
NATIVE := dsort_tpu/runtime/native

lint:  ## project-native static analysis (registry/concurrency/tracing/...)
	$(PY) -m dsort_tpu.cli lint

lint-sarif:  ## lint as SARIF 2.1.0 (code-scanning upload) -> lint.sarif
	$(PY) -m dsort_tpu.cli lint --format sarif > lint.sarif

baseline:  ## record current findings as tolerated (ship this file EMPTY)
	$(PY) -m dsort_tpu.cli lint --write-baseline

test:  ## tier-1 suite (excludes slow/sanitizer tests)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

spec-smoke:  ## explicit-state model check of the fleet protocol (bounded, backend-free, seconds)
	$(PY) -m dsort_tpu.cli spec check --max-states 12000

native:  ## build libdsort_native.so
	$(MAKE) -C $(NATIVE)

tsan:  ## build + run the native selftest under ThreadSanitizer
	$(MAKE) -C $(NATIVE) tsan-selftest
	$(NATIVE)/selftest_tsan

asan:  ## build + run the native selftest under AddressSanitizer
	$(MAKE) -C $(NATIVE) asan-selftest
	$(NATIVE)/selftest_asan

ubsan:  ## build + run the native selftest under UBSanitizer
	$(MAKE) -C $(NATIVE) ubsan-selftest
	$(NATIVE)/selftest_ubsan

sanitize: tsan asan ubsan  ## all three sanitizer selftest runs

.PHONY: lint lint-sarif baseline test spec-smoke native tsan asan ubsan sanitize
