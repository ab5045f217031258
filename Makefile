# Ops entry points (reference Makefile parity: build/test/run/verify,
# Makefile:29-57,186-214 — adapted to the TPU runtime: the "build" step is
# the native C++ data plane; agents need no docker images).

PY ?= python

.PHONY: all native test t1 test-native test-kernels bench chaos server dryrun verify clean analyze analyze-native

all: native

# C++ store + data plane (g++; loaded via ctypes)
native:
	$(MAKE) -C native

test: native
	$(PY) -m pytest tests/ -q

# tier-1 verify: the EXACT command from ROADMAP.md (the driver's gate) —
# CPU platform, non-slow suite, DOTS_PASSED echoed for the pass floor
t1:
	bash -c 'set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m "not slow" --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE "^[.FEsx]+( *\[ *[0-9]+%\])?$$" /tmp/_t1.log | tr -cd . | wc -c); exit $$rc'

# Invariant analysis plane (the merge gate next to t1 — docs/ANALYSIS.md):
# 1. repo-custom AST lint (ATP001..ATP005) against the checked-in
#    analysis/baseline.json ratchet — new violations fail, frozen ones
#    carry per-site justifications;
# 2. HLO contracts — never-all-gather sharding, donation aliasing, the
#    recompile budget over a scripted mixed workload (CPU tiny model);
# 3. analyzer self-tests (each rule's flag / don't-flag fixtures).
# Sanitizer stress on the native store is the heavyweight leg — run it on
# demand: `make analyze-native` (or ANALYZE_NATIVE=1 make analyze).
analyze:
	$(PY) -m agentainer_tpu.analysis
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_analysis.py tests/test_hlo_contracts.py \
	  tests/test_sp_decode_hlo.py tests/test_spec_verify_hlo.py tests/test_paged_hlo.py \
	  -q -p no:cacheprovider
	@if [ "$(ANALYZE_NATIVE)" = "1" ]; then $(MAKE) analyze-native; fi
	@echo "analyze: all legs passed"

# sanitizer-hardened native builds + the multi-threaded store/AOF stress
# harness under asan, tsan and ubsan (native/stress_store.cc)
analyze-native:
	$(MAKE) -C native sanitize

test-native: native
	$(PY) -m pytest tests/test_native.py tests/test_dataplane.py tests/test_store.py -q

test-kernels:
	$(PY) -m pytest tests/test_pallas_attention.py tests/test_models.py -q

# one JSON line: {"metric":..., "value":..., "unit":..., "vs_baseline":...}
bench: native
	$(PY) bench.py

# chaos soak: live daemon + engine subprocesses through the seeded fault
# schedule (store blips, SIGKILLs, slow dispatch, torn AOF, poisoned
# prefill, SIGKILL-mid-fused-decode-loop resume, replica-fleet
# failover/lease-flap/stale-routing phases);
# asserts the durability invariants and writes BENCH_chaos.json.
# Fixed seed -> reproducible schedule; full run drops ATPU_CHAOS_SMOKE
chaos:
	JAX_PLATFORMS=cpu ATPU_CHAOS_SEED=1337 ATPU_CHAOS_SMOKE=1 $(PY) scripts/chaos_soak.py

server: native
	$(PY) -m agentainer_tpu.cli server

# serve-time tp and MoE tp x ep engines on a virtual device mesh, then the
# two-process jax.distributed smoke
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"

# environment smoke test (reference `make verify` spirit)
verify:
	@$(PY) -c "import jax; print('jax', jax.__version__, jax.default_backend(), jax.devices())"
	@$(PY) -c "from agentainer_tpu.native import available; print('native store:', 'ok' if available() else 'MISSING')"
	@timeout 120 $(PY) -c "import jax.numpy as jnp; print('device exec:', float(jnp.add(1, 1)))" \
	  || echo "device exec: UNREACHABLE (listing can succeed while the compile service is wedged)"

clean:
	$(MAKE) -C native clean 2>/dev/null || true
	rm -rf native/build
