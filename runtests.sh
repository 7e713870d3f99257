#!/usr/bin/env bash
# Test runner (analog of the reference's runtests.sh — SURVEY §2.13).
# Runs the whole suite on a virtual 8-device CPU mesh; pass extra pytest
# args through, e.g. ./runtests.sh -k keras
set -euo pipefail
cd "$(dirname "$0")"
# --examples: the examples/ smoke tier (each walkthrough runs as a
# subprocess with DL4J_EXAMPLE_SMOKE=1 and must exit rc=0)
if [[ "${1:-}" == "--examples" ]]; then
  shift
  exec python -m pytest tests/test_examples.py -q -m slow "$@"
fi
# static-analysis tier (graftlint): host-sync patterns in the jit hot
# paths PLUS donation-safety / recompile-hazard / thread-discipline /
# tracer-leak over the whole package. Baseline-aware (the committed
# triage backlog doesn't fail; any NEW finding does) with a hard 10 s
# wall-clock budget so the pre-test tier stays fast.
# tools/check_host_sync.py remains as a back-compat shim over the
# host-sync rule.
python -m tools.graftlint --baseline tools/graftlint/baseline.json \
  --max-seconds 10
# no-chip refusal: chip_smoke.py (the on-chip check: ResNet-50 fit(),
# ServingEngine and the Pallas kernels on one TPU, run through the chip
# tool before every benchmark) and bench.py have no CPU configuration —
# where JAX finds no TPU they must exit non-zero before building a
# model. The suite itself stays on the CPU.
for script in chip_smoke.py bench.py; do
  if JAX_PLATFORMS=cpu python "$script" >/dev/null 2>&1; then
    echo "$script ran without a TPU: it must refuse"; exit 1
  fi
done
# perf tier: compiled-in telemetry WITH in-step histograms (the flight
# recorder's config) must stay within a 3% step-overhead budget on the
# CPU path — the observe/ "one fetch per flush interval" claim
JAX_PLATFORMS=cpu python -m benchmarks.telemetry_overhead \
  --steps 150 --with-histograms --assert-overhead --tolerance 0.03
# input-pipeline tier: the fed fit path must replay the unfed
# trajectory bitwise and leave host_to_device span evidence
# (correctness only — the timed fed-vs-unfed A/B is not CI-gated)
JAX_PLATFORMS=cpu python -m benchmarks.input_pipeline --smoke
# serving tier: engine outputs bitwise-equal to direct model.output,
# zero recompiles after the warmup sweep (watchdog-asserted), and
# pipelined dispatch >=1.3x the blocking dispatcher closed-loop
JAX_PLATFORMS=cpu python -m benchmarks.serving --smoke
# quantization tier: int8 serving arm answers within the top-1 budget
# of f32, every precision arm warm (zero post-warmup recompiles), and
# int8's bytes-moved-per-request proxy strictly below bf16's
JAX_PLATFORMS=cpu python -m benchmarks.serving --precision-ab --smoke
# fleet tier: multi-process Poisson soak through the front-door router
# (admission control + SLO shedding) — zero post-warmup recompiles,
# shed rate < 100%, served p99 under the CPU-calibrated bound
JAX_PLATFORMS=cpu python -m benchmarks.serving --smoke-fleet
# cluster tier: chaos soak through the multi-node tier — 2 worker-node
# subprocesses join a gossiped registry + shared artifact store; one is
# SIGKILLed mid-soak and rejoins under the same id (breaker opens and
# recovers, zero live compiles from the shared store), the other is
# SIGTERM-drained (finishes in-flight, deregisters, exits 0); client
# errors bounded by the killed node's in-flight window, p99 gated
JAX_PLATFORMS=cpu python -m benchmarks.serving --smoke-cluster
# chaos tier: deterministic fault injection under an armed DL4J_CHAOS
# plan — torn registry record classified dead then healed, corrupted
# AOT blob quarantined + live-compiled warm, chaos-delayed remote sends
# absorbed with zero client errors, broker drops + restart survived,
# same-seed replay bitwise identical; plus expired-deadline requests
# answered 504 at the front door WITHOUT device dispatch, and the
# graftlint chaos-hygiene baseline stays empty
JAX_PLATFORMS=cpu python -m benchmarks.serving --smoke-chaos
# retrieval tier: interleaved A/B over the fused distance+top-k path —
# jitted brute >= host VPTree qps on worst-case pruning-hostile
# queries over the same corpus (>=10x in the full 1M run), int8 and
# IVF recall@10 >= 0.95 vs the exact f32 oracle, repeated queries
# bitwise identical (including distance ties), zero live compiles
# after the warmup sweep, int8 bytes/query < 0.3x f32 and IVF < brute
JAX_PLATFORMS=cpu python -m benchmarks.neighbors --smoke
# retrieval-cluster tier: scatter-gather chaos — two serve
# --neighbors-index subprocesses own disjoint shard slices; one is
# SIGKILLed mid-stream (every in-flight query answers full or
# partial:true, never an exception), rejoins under the same id warm
# from the shared store with zero live compiles, full answers resume,
# and the survivor SIGTERM-drains to exit 0 deregistered
JAX_PLATFORMS=cpu python -m benchmarks.neighbors --smoke-cluster
# autotune tier: one measured sweep (interleaved A/B per tunable)
# persists a fingerprinted TunedConfig artifact; it must reload
# bit-for-bit, size a consumer engine whose outputs stay bitwise-equal
# to direct model.output, and warm a SECOND process from the shared
# store with zero live compiles; the nprobe recall floor must actually
# exclude a candidate (constraint, not preference) and the measured
# winner must be >= the hand-tuned default on the serving tunable
JAX_PLATFORMS=cpu python -m benchmarks.autotune --smoke
# elastic tier: with one straggler, bounded-staleness ASYNC_ELASTIC
# sustains >=1.5x the SYNC round rate with divergence under the
# hard-sync threshold, and reduces exactly to AVERAGING without one
JAX_PLATFORMS=cpu python -m benchmarks.elastic --smoke
# online tier: train-and-serve in one process — a broker-fed learner's
# improved params hot-promote into the warm executables within the
# window (zero recompiles, watchdog-asserted), a degraded candidate is
# rejected, a forced degrade is sentinel-rolled-back to bitwise params,
# and client p99 stays bounded through every swap
JAX_PLATFORMS=cpu python -m benchmarks.online --smoke
# generation tier: continuous-batching decode — 16 Poisson-staggered
# SSE streams through POST /api/generate, every greedy output bitwise-
# equal to the sequential reference decode with slots reused mid-flight,
# zero live compiles after warmup (watchdog-asserted), token p99 + TTFT
# under the CPU bounds, and the pretrained int8 head strictly fewer
# bytes/token than bf16 within the next-token agreement budget; plus
# the v2 serving modes: chunked prefill TTFT strictly below tick
# prefill at 256-token prompts (bitwise-equal output), the speculative
# stream bitwise-equal to plain decode on the pretrained artifact, and
# a session resumed on a second in-proc node from the shared store
# checkpoint — bitwise continuation with zero live compiles
JAX_PLATFORMS=cpu python -m benchmarks.generation --smoke
# native tier: build the C kernels when a toolchain exists, then gate
# the fused pair producer — native must be >= the numpy fallback in
# tokens/s AND hand the device a bitwise-identical dispatch stream
# (toolchain-less checkouts skip the build; the fallback tier below
# still proves the numpy path)
if command -v c++ >/dev/null 2>&1 || command -v g++ >/dev/null 2>&1; then
  make -s -C native
  JAX_PLATFORMS=cpu python -m benchmarks.baseline_suite \
    doc2vec_producer --native-ab --smoke
else
  echo "native tier: no C++ toolchain, skipping build + A/B gate"
fi
# fallback-forced tier: the pairgen suite re-run with the native
# library kill-switched off (DL4J_NATIVE=0) — the numpy producer must
# train every mode end-to-end on its own
DL4J_NATIVE=0 JAX_PLATFORMS=cpu python -m pytest tests/test_pairgen.py -q
exec python -m pytest tests/ -q "$@"
