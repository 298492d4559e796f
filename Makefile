# Native runtime build (≙ the reference's meson-built C core; here the
# native pieces are the util lib, the buffer ring, and custom-filter ABI
# examples — see csrc/).
CXX ?= g++
CXXFLAGS ?= -O2 -fPIC -Wall -Wextra -std=c++17
BUILD := build/native
SHELL := /bin/bash

LIB := $(BUILD)/libnnstpu.so
EXAMPLES := $(BUILD)/custom_passthrough.so $(BUILD)/custom_scaler.so

.PHONY: native clean test check tier1 lint racecheck flowcheck jitcheck \
	jit-stability chaos \
	chaos-zeroloss \
	chaos-fleet chaos-preempt chaos-llm chaos-elastic fuse-parity async-parity \
	shard-parity delta-parity package

native: $(LIB) $(EXAMPLES)

# `make check` = what CI runs on a clean checkout: native build + the
# non-slow test suite on the 8-virtual-device CPU mesh
# (tests/conftest.py forces JAX_PLATFORMS=cpu) + a packaging sanity
# check.
check: native lint racecheck flowcheck jitcheck
	python -m pytest tests/ -q -m 'not slow'
	python -c "import nnstreamer_tpu as nt; print('import ok:', len(nt.pipeline.registry.element_names()), 'elements')"
	$(MAKE) jit-stability
	$(MAKE) fuse-parity
	$(MAKE) async-parity
	$(MAKE) shard-parity
	$(MAKE) delta-parity
	$(MAKE) chaos
	$(MAKE) chaos-fleet
	$(MAKE) chaos-preempt
	$(MAKE) chaos-llm
	$(MAKE) chaos-elastic

# `make fuse-parity` = the fusion compiler's byte-parity oracle: every
# fusible pipeline in the corpus (plus a built-in representative suite)
# must produce byte-identical sink output fused and unfused
# (tools/fuse_parity.py exits nonzero on any divergence).
fuse-parity:
	env JAX_PLATFORMS=cpu python tools/fuse_parity.py

# `make async-parity` = the overlapped executor's byte-parity oracle:
# the same corpus, each pipeline run unfused with every tensor_filter
# forced to a 4-frame in-flight window vs in-flight=1 — the window must
# be invisible in the sink bytes (and in their order).
async-parity:
	env JAX_PLATFORMS=cpu python tools/fuse_parity.py --mode async

# `make shard-parity` = the sharded-serving parity oracle: every
# mesh-declaring pipeline in the corpus (plus a built-in representative
# suite) must produce the same sink output sharded across the
# 8-virtual-device mesh and single-chip: the same bytes, floats within
# SHARD_RTOL where the shardings sum in different orders
# (tools/shard_parity.py exits nonzero on any divergence, and on
# vacuous coverage).
shard-parity:
	env JAX_PLATFORMS=cpu python tools/shard_parity.py

# `make delta-parity` = the temporal-delta transport's byte-parity
# oracle: a built-in stream suite (motion, static, promotion, layout
# change, bitwise NaN payloads, bf16 composition, live socket) run over
# a negotiated wire-codec=delta link vs a raw control link — decoded
# bytes must be identical, and the suite must actually ship sparse
# diffs (tools/delta_parity.py exits nonzero on divergence and on
# vacuous coverage).
delta-parity:
	env JAX_PLATFORMS=cpu python tools/delta_parity.py

# `make chaos` = the full fault-injection harness: the slow seeded
# serve-pipeline schedules (excluded from tier-1 by the slow marker)
# plus the zero-loss link-kill/peer-kill scenarios — sessions must
# survive >=3 mid-stream kills (incl. mid-DATA_BATCH) with exact
# accounting. Run on demand and at the end of `make check`.
chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q

# just the zero-loss acceptance scenarios (fast; they also run in tier-1)
chaos-zeroloss:
	env JAX_PLATFORMS=cpu python -m pytest \
		tests/test_chaos.py::TestZeroLossChaos -q

# `make chaos-fleet` = the fleet-failover acceptance run (slow-marked,
# excluded from tier-1): 4 broker-registered replicas behind the router,
# 8 concurrent client streams, one replica killed mid-run and one
# administratively drained — every frame must settle RESULT xor SHED
# with zero declared losses and zero stream aborts.
chaos-fleet:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_router.py -q -m slow

# `make chaos-preempt` = the preemption acceptance run (slow-marked,
# excluded from tier-1): kill -TERM a training process mid-run and a
# fleet replica mid-serving — the trainer must resume at the exact
# recorded epoch (no repeated or skipped optimizer updates) and the
# resurrected replica must rejoin with the router's ledger balancing
# exactly.
chaos-preempt:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_checkpoint.py -q -m slow

# `make chaos-llm` = the disaggregated-LLM acceptance run (slow-marked,
# excluded from tier-1): a decode replica is killed mid-stream after a
# wire KV handoff; a fresh replica restores its snapshot and the
# re-shipped prompt must resume with EXACT token continuity (zero
# tokens lost or duplicated vs the monolithic greedy reference).
chaos-llm:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_llm_disagg.py -q -m slow

# `make chaos-elastic` = the elastic-fleet acceptance run (slow-marked,
# excluded from tier-1): random SIGTERMs under load with zero declared
# loss and both conservation ledgers balancing, a blue/green version
# swap mid-traffic (every frame settles, the fleet ends all-green), and
# the compile-cache warm-start budget (first frame <= 2x steady, with a
# cold control arm proving the gap is real).
chaos-elastic:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -m slow

# `make tier1` = the exact ROADMAP.md tier-1 verify gate, verbatim
# (timeout, log tee, pass-dot count and all).
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# `make racecheck` = the concurrency gate: the package's own sources
# must carry no lockset / lock-order / blocking-under-lock findings
# (deliberate, reasoned suppressions excepted). The JSON report lands
# in build/racecheck.json for CI artifacts.
racecheck:
	env JAX_PLATFORMS=cpu python -m nnstreamer_tpu racecheck nnstreamer_tpu -o build/racecheck.json

# `make flowcheck` = the settlement gate: every acquire (window slot,
# KV block, accepted socket) must reach a settle on every path, every
# discarding settle must bump a declared loss counter, and every
# declared conservation identity must be producible from the counters
# its module actually increments. --min-acquire-sites guards against a
# refactor silently unhooking the model (a scan that sees nothing finds
# nothing). JSON report lands in build/flowcheck.json for CI artifacts.
flowcheck:
	env JAX_PLATFORMS=cpu python -m nnstreamer_tpu flowcheck nnstreamer_tpu --min-acquire-sites 10 -o build/flowcheck.json

# `make jitcheck` = the compile/host-sync gate: no hidden host syncs,
# retrace hazards, donation-after-use, or impure compiled bodies in the
# hot path (reasoned # jitcheck: ok() suppressions excepted).
# --min-hot-sites guards against a refactor silently unhooking the
# role model. JSON report lands in build/jitcheck.json for CI.
jitcheck:
	env JAX_PLATFORMS=cpu python -m nnstreamer_tpu jitcheck nnstreamer_tpu --min-hot-sites 20 -o build/jitcheck.json

# `make jit-stability` = the runtime half of jitcheck: the builtin
# corpus runs to steady state twice against one persistent CompileCache
# — any second-pass frame-path compilation, any observed compile kind
# the static scan can't see, or a corpus that recorded no signatures at
# all fails the gate (tools/jit_stability.py).
jit-stability:
	env JAX_PLATFORMS=cpu python tools/jit_stability.py

# `make lint` = static gates: bytecode-compile the package, then run
# pipelint over every pipeline description in tests/ and README.md
# (tools/lint_corpus.py exits nonzero on any severity=error finding).
lint:
	python -m compileall -q nnstreamer_tpu tools
	env JAX_PLATFORMS=cpu python tools/lint_corpus.py

package:
	python -m pip wheel --no-deps --no-build-isolation -w build/dist . \
	  || python setup.py bdist_wheel 2>/dev/null \
	  || echo "wheel build unavailable; pyproject metadata still valid"

$(BUILD):
	mkdir -p $(BUILD)

# link to a private name, then rename: `queue backend=auto` builds on
# first use, and two processes starting on a clean clone must never
# load each other's half-written library
$(LIB): csrc/nns_util.cc csrc/nns_ring.cc csrc/nns_custom.h | $(BUILD)
	$(CXX) $(CXXFLAGS) -shared -o $@.$$$$.tmp csrc/nns_util.cc csrc/nns_ring.cc \
		&& mv -f $@.$$$$.tmp $@

$(BUILD)/custom_%.so: csrc/custom_%.cc csrc/nns_custom.h | $(BUILD)
	$(CXX) $(CXXFLAGS) -shared -o $@.$$$$.tmp $< && mv -f $@.$$$$.tmp $@

test: native
	python -m pytest tests/ -q

clean:
	rm -rf $(BUILD)
