PROTOC ?= protoc

.PHONY: proto test tier1 native bench lint chaos clean

proto:
	$(PROTOC) -Iseldon_core_tpu/proto --python_out=seldon_core_tpu/proto \
		seldon_core_tpu/proto/tf_compat.proto \
		seldon_core_tpu/proto/tfserving_compat.proto \
		seldon_core_tpu/proto/seldon.proto
	# protoc emits flat top-level imports; rewrite to package-relative
	sed -i 's/^import \(tf_compat_pb2\|tfserving_compat_pb2\)/from seldon_core_tpu.proto import \1/' \
		seldon_core_tpu/proto/seldon_pb2.py \
		seldon_core_tpu/proto/tfserving_compat_pb2.py

native:
	$(MAKE) -C native

# the fast tier (pyproject addopts excludes @slow) in ONE process that
# stops at the first failure: a convenience for a file or two
# (`make test ARGS=tests/test_faults.py`), not the verdict — the whole
# tier takes over an hour this way
test:
	python -m pytest $(or $(ARGS),tests/) -x -q

# the verdict: the driver's own command, letter for letter as
# /root/TESTS_LAST_RUN.json `commands` gives it (six workers, a file a
# worker at a time, passes counted from the junit file; it writes
# /tmp/_t1.log and /tmp/_t1.xml).  Wall on the builder's 8-core sandbox:
# 832 s at PR 44 (ROADMAP D15 has the table by file)
tier1: SHELL := /bin/bash
tier1:
	@set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; said=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=$${said:-$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $$rc

# everything, including the compile-heavy @slow modules (~20 min here)
test-all:
	python -m pytest tests/ -x -q -m 'slow or not slow'

bench:
	python bench.py

# static-invariant suite (tools/graftlint): jit purity, knob registry,
# lock discipline, metrics contract, propagation, exception hygiene.
# Also runs inside tier-1 (tests/test_graftlint.py) and stamps
# lint_violations on the bench compact line.
lint:
	python -m tools.graftlint

# resilience suite: fault injection, self-healing transport, DCN chaos,
# live migration/failover, watchdog/quarantine (fast tier only)
chaos:
	python -m pytest tests/test_faults.py tests/test_selfheal.py \
		tests/test_chaos_dcn.py tests/test_migration.py \
		tests/test_watchdog.py -q -m 'not slow'

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
	$(MAKE) -C native clean 2>/dev/null || true
