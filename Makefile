CXX ?= g++
CXXFLAGS ?= -O2 -g -std=c++17 -fPIC -Wall -Wextra -pthread
BUILD := build
LIB := $(BUILD)/libparsec_core.so

all: $(LIB)

SRCS := native/core.cpp native/sched.cpp native/comm.cpp
HDRS := native/parsec_core.h native/runtime_internal.h native/lockfree.h

$(LIB): $(SRCS) $(HDRS)
	@mkdir -p $(BUILD)
	$(CXX) $(CXXFLAGS) -shared -o $@ $(SRCS)

clean:
	rm -rf $(BUILD)

# ThreadSanitizer build of the core (the lock-free scheduler path's
# correctness harness; see tools/stress_tsan.py).  Loaded via
# PTC_NATIVE_LIB with the tsan runtime LD_PRELOADed.
TSAN_LIB := $(BUILD)/libparsec_core_tsan.so

tsan: $(TSAN_LIB)

$(TSAN_LIB): $(SRCS) $(HDRS)
	@mkdir -p $(BUILD)
	$(CXX) -O1 -g -std=c++17 -fPIC -Wall -pthread -fsanitize=thread \
		-shared -o $@ $(SRCS)

# UndefinedBehaviorSanitizer build + the stress_tsan job set (the same
# concurrency workloads, here hunting signed overflow / bad shifts /
# misaligned access in the spec decoder and dep engine).  halt_on_error
# + no-recover: the first report fails the run.
UBSAN_LIB := $(BUILD)/libparsec_core_ubsan.so

$(UBSAN_LIB): $(SRCS) $(HDRS)
	@mkdir -p $(BUILD)
	$(CXX) -O1 -g -std=c++17 -fPIC -Wall -pthread \
		-fsanitize=undefined -fno-sanitize-recover=all \
		-shared -o $@ $(SRCS)

ubsan: $(UBSAN_LIB)
	PTC_NATIVE_LIB=$(UBSAN_LIB) \
	LD_PRELOAD=$$($(CXX) -print-file-name=libubsan.so) \
	UBSAN_OPTIONS="halt_on_error=1 print_stacktrace=1 exitcode=67" \
	timeout 900 python tools/stress_tsan.py

# Curated clang-tidy pass over the native core (.clang-tidy: bugprone-*
# + concurrency-* + performance-*).  Gated: containers without
# clang-tidy skip with a notice instead of failing the check recipe.
tidy:
	@if command -v clang-tidy >/dev/null 2>&1; then \
		clang-tidy --quiet $(SRCS) -- -std=c++17 -pthread; \
	else \
		echo "tidy: clang-tidy not installed; skipped" \
		     "(config committed in .clang-tidy)"; \
	fi

# Static dataflow verification of every in-tree graph generator
# (tools/verify_graphs.py -> parsec_tpu/analysis rules V001-V009).
# Exit 1 = a graph regressed the clean baseline.
verify-graphs: $(LIB)
	python tools/verify_graphs.py

# Static resource & schedule analysis of every in-tree graph generator
# (tools/plan_graphs.py -> parsec_tpu/analysis/plan.py): every graph
# must plan CLEAN (no enumeration refusal, finite residency/makespan
# bounds) and the potrf bench tiling must plan inside its latency
# budget.  Emits PLAN_graphs.json (bench_check guards potrf_nt16_ms).
plan-graphs: $(LIB)
	python tools/plan_graphs.py --json PLAN_graphs.json

# Transfer-economics sweep (tools/testbandwidth.py): eager / rendezvous
# / PK_DEVICE paths on loopback, fitted fixed-overhead + per-byte cost,
# BENCH-style JSON.  The ranks run on the CPU backend.
bench-comm: $(LIB)
	python tools/testbandwidth.py --json BENCH_comm.json

# Dispatch-latency suite (bench.py --dispatch --json): single-chain +
# contended successor-begin percentiles with sched_stats evidence
# (bypass hits, freelist hit rate, inject traffic) and host provenance
# (cpu_count vs workers — oversubscribed runs are flagged, not silently
# reported).  Rung-1 of the measurement ladder.
bench-dispatch: $(LIB)
	python bench.py --dispatch --json BENCH_dispatch.json

# Device-pipeline suite (bench.py --device --json): staged-vs-prefetched
# wave dispatch (per-wave h2d stall off the DEVICE span aux, overlap
# fraction from paired DEVICE/H2D spans) + the 2x-budget out-of-core
# GEMM, with host provenance and an oversubscription flag.  Runs on the
# CPU jax backend — no TPU needed.
bench-device: $(LIB)
	python bench.py --device --json BENCH_device.json

# Cross-rank streaming sweep (bench.py --stream --json): steady-state
# >=4 MiB device-to-device tile latency with the wire-v4 streaming
# pipeline (progressive serve + 2 rails) vs the serialized baseline
# (stream off, 1 rail), rails=1 vs rails=2 throughput, and per-hop
# d2h/wire overlap evidence.  Loopback, CPU jax backend — no TPU needed.
bench-stream: $(LIB)
	python bench.py --stream --json BENCH_stream.json

# Runtime-native collective suite (bench.py --collective --json):
# DAG-dependency chain reduction vs runtime-native streamed collective
# across message sizes on a 2-rank pair, the whole-array XLA shard_map
# psum baseline, and the level-2 trace evidence (comm_wait+coll_wait
# lost time, compute/wire overlap fraction) for the largest size.
# Loopback, CPU jax backend — no TPU needed.
bench-collective: $(LIB)
	python bench.py --collective --json BENCH_collective.json

# Serving-runtime suite (bench.py --serve --json): mixed-tenant
# latency p50/p99 (hi-priority tenant vs a no-QoS control over the SAME
# request mix), admission rejects under tight budgets, and the
# continuous-batching decode's bit-exactness vs the sequential
# per-request baseline.  CPU-only — no TPU needed.
bench-serve: $(LIB)
	python bench.py --serve --json BENCH_serve.json

# Self-driving-runtime suite (bench.py --control --json, ptc-pilot):
# the drift soak — a stale device-cache knob vector lands mid-run with
# PTC_COMM_FAULT_DELAY_US armed, the controller detects the sustained
# makespan drift, re-simulates on the recalibrated cost model and
# hot-swaps the winner at the next pool boundary (recovered-throughput
# ratio gated >= 0.5, no restart) — plus the adaptive-vs-fixed spec_k
# sweep over a mixed oracle/adversarial draft workload (deterministic
# score; bit-identity never relaxed).  CPU-only — no TPU needed.
bench-control: $(LIB)
	python bench.py --control --json BENCH_control.json

# Topology-tier soak (bench.py --topo --json, ptc-topo): the 4-rank
# two-island mesh under the island emulator's per-peer recv delays —
# ring vs hierarchical all_reduce (bit-exact, per-class wire split),
# and the rank-remap chain: measured DCN bytes identity vs
# run(remap=True) (>= 30% reduction enforced), plan-predicted per-class
# bytes sound vs the classed wire_out_bound.  CPU-only, loopback.
bench-topo: $(LIB)
	python bench.py --topo --json BENCH_topo.json

# Tracing-overhead ladder (bench.py --trace --json): per-task cost at
# trace levels 0/1/2 and the flight-recorder ring vs unbounded buffers
# at level 1 (the PR2 one-transaction-per-task contract), plus the
# always-on metrics on/off cost at level 0, with host provenance.
# No TPU needed.
bench-trace: $(LIB)
	python bench.py --trace --json BENCH_trace.json

# Bench-trajectory regression guard (the CI gate): compares the working
# tree's BENCH_*.json against the committed copies with per-metric
# tolerances (dispatch p50, stream overlap_fraction, trace ring ratio
# and level-0 cost, coll ratios, device stall reduction), honoring each
# file's recorded `oversubscribed` flag.  Run the bench suite first,
# then this; exit 1 = a guarded metric regressed.
bench-check:
	python tools/bench_check.py

# ptc-tune gate (tools/ptc_tune.py --check): every in-tree graph must
# plan concretely (no enumeration refusal), carry an explicit wave-
# fusability certify/refuse verdict per wave (no silent skips), and
# simulate to a finite, bit-reproducible makespan under the default
# knob vector.  Exit 1 = a graph regressed the gate.
tune-check: $(LIB)
	python tools/ptc_tune.py --check

# ptc-blackbox smoke: the postmortem assembler over a committed
# fixture (two survivor journals for a 3-rank incident) must produce a
# byte-stable incident report — dead rank, first cause, holdings.
# Deterministic, no runtime needed; exit 1 = report drift.
postmortem-smoke:
	python tools/ptc_postmortem.py tests/data/blackbox_fixture \
		--expect tests/data/blackbox_fixture/expected.json > /dev/null

# Default check recipe: bench-trajectory guard + graph hygiene (verify
# + plan + tune baselines) + postmortem smoke + native lint —
# regressions in any fail fast.
check: bench-check verify-graphs plan-graphs tune-check postmortem-smoke tidy

.PHONY: all clean tsan ubsan tidy verify-graphs plan-graphs tune-check \
	check bench-comm bench-dispatch bench-device bench-stream \
	bench-collective bench-trace bench-serve bench-topo \
	bench-control bench-check postmortem-smoke
