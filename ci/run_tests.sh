#!/usr/bin/env bash
# CI entry point (reference: tests/travis/run_test.sh + Jenkinsfile matrix,
# SURVEY §2.7/§4.7). Stages mirror the reference's: build native libs,
# unit suite on the virtual 8-device CPU mesh, multi-chip dry-run compile,
# example smoke runs (included in the suite), lint-lite.
#
# Tiers (reference unittest-vs-nightly split, SURVEY §4):
#   ci/run_tests.sh          quick tier: everything except the exhaustive
#                            registry sweeps (completeness gates included)
#   ci/run_tests.sh --full   nightly tier: the whole suite
set -euo pipefail
cd "$(dirname "$0")/.."

TIER="quick"
if [[ "${1:-}" == "--full" ]]; then
    TIER="full"
fi

echo "== stage 1: native build =="
make -C native -j"$(nproc)"

echo "== stage 2: unit + integration suite ($TIER tier, virtual 8-device CPU mesh) =="
if [[ "$TIER" == "quick" ]]; then
    python -m pytest tests/ -q -m "not slow"
else
    python -m pytest tests/ -q
fi

echo "== stage 3: parallel tests (8-device CPU simulation, -m parallel) =="
# Dedicated pass over the multi-device tests (ZeRO-1 sharded update,
# sharding round-trips, kvstore sharded push/pull). conftest.py forces the
# 8-virtual-device CPU mesh; the explicit env makes the stage independently
# reproducible: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
python -m pytest tests/ -q -m parallel

echo "== stage 4: multi-chip sharding dry-run (8 virtual devices) =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== stage 5: serving tests (dynamic batching + bucketed compile cache) =="
# Dedicated pass over the inference-server suite: concurrency-sensitive
# (batch former windows, deadlines, engine-dispatch pipelining), so it gets
# its own stage where a hang or flake is attributable. Then the end-to-end
# dry-run: concurrent clients -> occupancy/cache-hit assertions.
JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py tests/test_serving_generate.py tests/test_paged_decode.py tests/test_quant.py tests/test_spec_decode.py tests/test_http_frontend.py -q
# Both end-to-end dry-runs below run with the engine happens-before
# sanitizer ON: the serving/decode dispatch paths must produce ZERO race
# reports (docs/concurrency.md sanitizer section).
JAX_PLATFORMS=cpu MXNET_ENGINE_SANITIZER=1 python -c "
import __graft_entry__ as g; g.dryrun_serving()
from mxnet_tpu import engine
assert engine.sanitizer_reports() == [], engine.sanitizer_reports()
print('sanitizer: 0 reports (serving)')"
# Continuous-batching decode gate: staggered generate streams must emit
# token streams identical to sequential generation, with fresh compiles
# bounded by the fixed program set and a clean mid-stream drain. Includes
# the paged-KV wave (ISSUE 13): shared-prefix streams at fixed KV bytes
# must run >= 2x the unpaged slot-equivalent co-residency, save >= 50% of
# prefill tokens via shared blocks, stay bitwise-identical to the unpaged
# arm, and add zero steady-state compiles — all sanitizer-clean.
# The compile witness rides along (ISSUE 18): the warm paged wave flips
# witness.steady_state() and must record ZERO fresh compiles after it.
JAX_PLATFORMS=cpu MXNET_ENGINE_SANITIZER=1 MXNET_COMPILE_WITNESS=1 python -c "
import __graft_entry__ as g; g.dryrun_decode()
from mxnet_tpu import engine
assert engine.sanitizer_reports() == [], engine.sanitizer_reports()
print('sanitizer: 0 reports (decode)')"
# Warm-restart gate (persistent progcache): a cold process populates the
# cache and tunes its ladder, then a SECOND process over the same cache
# dir must serve the same traffic with 0 fresh bucket compiles (ladder
# disk-loaded before traffic) and bitwise-identical outputs.
JAX_PLATFORMS=cpu python -c "import __graft_entry__ as g; g.dryrun_progcache()"
# Quantized-inference gate (ISSUE 14): int8-weight + int8-KV paged decode
# streams must be bitwise-identical to sequential quantized generation and
# track the f32 arm's greedy tokens (first-token exact, LCP >= 60%) inside
# the unchanged paged program bound; the MLP serving pair must hit >= 99%
# top-5 agreement vs f32 with a warm restart disk-loading the quantized
# programs at ZERO fresh compiles — all sanitizer-clean.
JAX_PLATFORMS=cpu MXNET_ENGINE_SANITIZER=1 python -c "
import __graft_entry__ as g; g.dryrun_quant()
from mxnet_tpu import engine
assert engine.sanitizer_reports() == [], engine.sanitizer_reports()
print('sanitizer: 0 reports (quant)')"
# Speculative-decoding gate (ISSUE 16): staggered greedy spec streams
# (int8 self-draft, k=4, one fixed-shape verify) must be token-identical
# to vanilla decode inside ladder+2 programs at >= 1.5 tokens committed
# per scheduler step; sampled streams must match vanilla's per-position
# token distributions over 160 fixed seeds (rejection-sampling
# equivalence) and reproduce bitwise under the same seed; a warm restart
# over the same progcache dir serves identical streams with ZERO fresh
# compiles — all sanitizer-clean.
JAX_PLATFORMS=cpu MXNET_ENGINE_SANITIZER=1 python -c "
import __graft_entry__ as g; g.dryrun_spec()
from mxnet_tpu import engine
assert engine.sanitizer_reports() == [], engine.sanitizer_reports()
print('sanitizer: 0 reports (spec)')"
# HTTP front-end gate (ISSUE 17): a subprocess serves the predict +
# generate front-ends; concurrent HTTP clients, a 2x overload burst that
# must shed FAST with 429s (no queue-and-expire timeouts), a SIGTERM
# mid-stream drain that drops zero tokens, and a warm restart over the
# same progcache dir at ZERO fresh compiles with identical greedy
# streams. MXNET_ENGINE_SANITIZER=1 is inherited by the serve arms, and
# so is MXNET_COMPILE_WITNESS=1: the warm serve arm flips
# witness.steady_state() once ready and must report 0 compiles after it.
JAX_PLATFORMS=cpu MXNET_ENGINE_SANITIZER=1 MXNET_COMPILE_WITNESS=1 \
    python -c "import __graft_entry__ as g; g.dryrun_http()"
# Tracing + flight-recorder gate (ISSUE 19): traced traffic must leave
# assembled span trees addressable by request id with the same trace_id
# surfacing as an OpenMetrics exemplar on the latency histogram; one
# forced deadline miss must write EXACTLY one diagnostic bundle carrying
# the victim's queued span and bump
# flight_bundles_total{trigger="deadline_miss"}.
JAX_PLATFORMS=cpu MXNET_ENGINE_SANITIZER=1 \
    python -c "import __graft_entry__ as g; g.dryrun_flight()"

echo "== stage 6: import hygiene =="
python - <<'EOF'
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import mxnet_tpu as mx
assert mx.libinfo.find_lib_path()
print("import OK; ops:", len(mx.ops.registry.OP_REGISTRY))
EOF

echo "== stage 7: static analysis (lock-order / engine / purity / progcache-io / racecheck / compilesurface) =="
# Pure-AST gate, independent of the pytest tiers: the shipped tree must
# produce no findings beyond ci/analysis_baseline.json (each baselined
# entry carries a written justification). Fails on ANY new finding.
# Budget: the full-tree pass must finish inside 15s (docs/static_analysis.md).
timeout -k 5 15 env JAX_PLATFORMS=cpu python -m mxnet_tpu.analysis --fail-on-new
# Self-check: the known-bad fixtures must trip the gate (a silently
# lobotomized analyzer would otherwise pass CI forever).
for bad in abba_deadlock undeclared_mutable impure_jit telemetry_in_jit \
        raw_write_progcache undeclared_var_access unfenced_host_read \
        var_use_after_delete weight_closure stray_jit donated_arg_reuse \
        undeclared_budget; do
    if JAX_PLATFORMS=cpu python -m mxnet_tpu.analysis \
            --root "tests/fixtures/analysis/${bad}.py" \
            --baseline none --fail-on-new >/dev/null 2>&1; then
        echo "analysis self-check FAILED: ${bad}.py not flagged" >&2
        exit 1
    fi
done
JAX_PLATFORMS=cpu python -m mxnet_tpu.analysis \
    --root tests/fixtures/analysis/clean_locks.py --baseline none --fail-on-new

echo "== stage 8: fault-injection dry-run (kill-a-rank recovery, CPU) =="
# Elastic-training gate: under a deterministic MXNET_FAULT_PLAN a
# supervised run loses rank 1 mid-training, restores the last committed
# async sharded checkpoint and replays to BIT-IDENTICAL weights; the
# dp=4 -> 2 -> 4 resharding round-trip is checked bitwise in the same
# entry point (docs/fault_tolerance.md).
# The sanitizer rides along: fault injection + recovery must not surface
# any undeclared access — races and injected faults are distinct defects.
JAX_PLATFORMS=cpu MXNET_FAULT_PLAN="kill_rank rank=1 step=5" \
    MXNET_ENGINE_SANITIZER=1 python -c "
import __graft_entry__ as g; g.dryrun_fault_tolerance()
from mxnet_tpu import engine
assert engine.sanitizer_reports() == [], engine.sanitizer_reports()
print('sanitizer: 0 reports (fault dryrun)')"
# Composed dp×pp gate (ISSUE 15): ZeRO-sharded data parallelism (data=4)
# composed with 1f1b pipeline stages (pipe=2) in one shard_map program,
# run under TrainingSupervisor with the same kill-a-rank plan — replay
# must be BITWISE identical to an uninterrupted run, and the final
# checkpoint must reshard dp=4 -> 2 -> 4 bitwise.
JAX_PLATFORMS=cpu MXNET_FAULT_PLAN="kill_rank rank=1 step=5" \
    MXNET_ENGINE_SANITIZER=1 python -c "
import __graft_entry__ as g; g.dryrun_composed_fault()
from mxnet_tpu import engine
assert engine.sanitizer_reports() == [], engine.sanitizer_reports()
print('sanitizer: 0 reports (composed dp x pp fault dryrun)')"

echo "ALL CI STAGES PASSED"
