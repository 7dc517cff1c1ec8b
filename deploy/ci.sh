#!/usr/bin/env bash
# CI gate, runnable locally or from .github/workflows/ci.yml:
#   ./ci.sh [fast|kernels|chaos|search|perf|loadtest|multichip|streaming|obs|trace|rebalance|curves]
#   (default: fast)
#
#   fast mode:
#   1. compileall lint gate — every .py in the package, tests, and
#      benchmarks must byte-compile (catches syntax/indent rot with no
#      deps beyond the stdlib);
#   2. tier-1 fast suite — the ROADMAP.md verify command: pytest on the
#      virtual 8-device CPU mesh, slow (subprocess/chaos/minutes-long)
#      suites excluded. This includes the PR-8 data-plane suites
#      (tests/test_stage_cache.py: single-flight staging, refcount/LRU
#      eviction, fingerprint collision safety, CS230_STAGE_CACHE=0
#      parity; tests/test_prewarm.py: hint derivation, yield-to-work,
#      never-warm-twice, /subscribe handshake);
#   3. sharded control-plane smoke — 2 coordinator-shard subprocesses
#      behind a stateless front end (runtime/frontend.py), reduced client
#      count, asserting completion + routing (no absolute-latency gate),
#      so the front/core split topology is exercised on every run.
#
#   loadtest mode (nightly/dispatch in ci.yml): the FULL 4-shard
#   control-plane load test (benchmarks/loadtest.py, ROADMAP item 2
#   harness) with the functional smoke gate; the fresh
#   loadtest_4shard.json is uploaded as a workflow artifact.
#
#   kernels mode: the interpret-mode kernel-parity suites ONLY — every
#   Pallas kernel (packed/masked logreg gradients, the fused packed
#   Nesterov step incl. its aliasing + convergence-mask-edge contracts,
#   level histogram, MLP epoch, KNN top-k) against its XLA reference on
#   CPU, plus the valve plumbing (CS230_MASKED_GRAD / CS230_FUSED_STEP /
#   CS230_HIST_KERNEL) end to end. A few minutes; the job that makes a
#   TPU-kernel regression fail without a TPU. Recipe + parity
#   contracts: docs/KERNELS.md.
#
#   search mode: the adaptive-search suites standalone (docs/SEARCH.md) —
#   the ASHA/Hyperband controller unit suite plus the e2e cluster runs
#   (prune mid-flight, degenerate-eta winner parity, the rung
#   journal-replay drill), then the committed adaptive-search benchmark
#   (ASHA vs exhaustive RandomizedSearch on the covertype config; gate:
#   score parity ±1e-3 AND <= 0.5x device-seconds) which refreshes
#   benchmarks/ADAPTIVE_SEARCH.json into bench-artifacts/.
#
#   perf mode (manually-triggered + nightly in ci.yml, like chaos): the
#   valve A/B regression harness (benchmarks/perf_observatory.py) in
#   quick mode with the noise-aware gate against a
#   benchmarks/PERF_OBSERVATORY.json baseline (none is committed since
#   PR 21: the comparator skips until one is measured) —
#   followed by an injected-regression drill (PERF_OBS_INJECT) proving
#   the gate itself still trips. Fresh measurements always land in
#   bench-artifacts/PERF_OBSERVATORY.json for upload.
#
#   multichip mode: the elastic-trial-fabric gate (docs/ARCHITECTURE.md
#   "Elastic trial fabric"). The mesh cache-parity + resharding suites
#   plus the scaling harness at 1/2 forced host devices (quick reps, no
#   >1.0x gate — the smoke proves the harness end to end). The nightly
#   ci.yml job additionally runs the FULL 1/2/4/8 curve and uploads the
#   fresh MULTICHIP_BENCH JSON for trend-watching.
#
#   streaming mode (every push in ci.yml, fast): the out-of-core
#   row-block streaming suites (tests/test_streaming.py — block-plan
#   parity, streamed-vs-single-shot score parity incl. the bitwise tree
#   pin, prefetch pinning, the CS230_STAGE_STRICT OOM repro — plus
#   tests/test_stage_cache.py, whose acquire/release + overflow-signal
#   contracts the streamer rides). With STREAMING_FULL=1
#   (nightly/dispatch) it additionally runs the full-geometry
#   benchmarks/streaming_micro.py (10x-budget OOM repro + double-buffer
#   overlap profile) and uploads the fresh STREAMING_MICRO.json.
#
#   obs mode (every push in ci.yml, fast): the fleet-health-plane gate
#   (docs/OBSERVABILITY.md "Fleet health plane") — the alert-engine /
#   capacity-signal unit suites (tests/test_fleet_health.py: burn-rate
#   windows, counter-reset clamping, hysteresis/drain gating, the pinned
#   stage_cache_overflow fire), the front-end aggregation suites
#   (tests/test_frontend_aggregation.py: merged Prometheus exposition,
#   /events cursor paging, /alerts union, /autoscale sums against fake
#   shards), and the flight-recorder metric/event catalog parity gates —
#   then the live overload→fire→drain→resolve drill
#   (benchmarks/fleet_health.py) on a real 2-shard fleet through the
#   front end, refreshing FLEET_HEALTH.json into bench-artifacts/ (the
#   committed acceptance artifact is benchmarks/FLEET_HEALTH.json).
#
#   trace mode (every push in ci.yml, fast): the critical-path /
#   trace-export gate (docs/OBSERVABILITY.md "Critical path & trace
#   export") — the engine unit suites (tests/test_critpath.py: exact
#   segment tiling, untraced-gap honesty, reclaim-wait + speculative-win
#   attribution, Perfetto/OTLP document shapes, the span-drop counter)
#   and the two-process stitching suite (tests/test_trace_propagation.py:
#   frontend.proxy roots the trace, X-Parent-Span nesting) — then the
#   live attribution drill (benchmarks/critical_path.py: baseline vs
#   injected-aggregate-slowdown through a real front end; gates segments
#   ≈ store wall and ≥80 % of the delta attributed), refreshing
#   CRITICAL_PATH.json into bench-artifacts/ and re-validating the
#   Perfetto export the drill wrote as loadable Chrome trace JSON.
#
#   curves mode (every push in ci.yml, fast): the trial-telemetry-plane
#   gate (docs/OBSERVABILITY.md "Trial telemetry plane") — the curve
#   capture/store/watchdog suites (tests/test_telemetry_curves.py: trace-tail ==
#   reported-score parity across fused+legacy scan bodies, stride
#   downsampling at non-multiple max_iter, the CS230_CURVES=0 strict
#   no-op pin, the live-socket watchdog e2e, curve-op journal truncation
#   fuzz, the SSE curve round-trip through a front end) plus the search
#   e2e suite whose diverged-terminal arithmetic curves ride. With
#   CURVES_FULL=1 (nightly/dispatch) it additionally runs
#   benchmarks/curve_micro.py (capture-overhead <= 3% gate, the
#   diverging-lr <30%-budget watchdog drill, survivor parity) and
#   uploads the fresh CURVE_MICRO.json (the committed acceptance
#   artifact is benchmarks/CURVE_MICRO.json).
#
#   rebalance mode (every push in ci.yml, fast): the cross-shard
#   rebalancing gate (docs/ROBUSTNESS.md "Shard rebalancing") — the
#   fencing/tombstone/forwarding unit suite (tests/test_rebalance.py:
#   migrate_out/migrate_in/steal journal round-trips + crash-point
#   truncation fuzz, steal-grant fencing and lease reclaim, the 409
#   forwarding stamp and the front end's bounded-TTL redirect cache,
#   live HTTP migration between two coordinators). With
#   REBALANCE_FULL=1 (nightly/dispatch) it additionally runs the full
#   skewed-hash load test (benchmarks/loadtest_skew.py --check: 80/20
#   session skew must recover >= 0.8x the even-hash jobs/s with the
#   rebalancer demonstrably acting) and uploads the fresh
#   LOADTEST_SKEW.json (the committed acceptance artifact is
#   benchmarks/LOADTEST_SKEW.json).
#
#   chaos mode (manually-triggered + nightly in ci.yml): the slow-marked
#   chaos/durability suites — fleet kill-mid-job, hung-worker lease
#   reclaim, the coordinator-SIGKILL drill (server subprocess killed
#   mid-job + restarted against the same journal dir; agents reconnect
#   and flush buffers — docs/ROBUSTNESS.md "Coordinator recovery"),
#   SPMD host loss, supervisor restart policy — which the fast gate
#   never runs. The drill writes its journal dir + process logs under
#   $CI_ARTIFACTS_DIR/coordinator_kill, so a red run uploads the
#   coordinator's jobs.jsonl and flight-recorder events.jsonl.
#
# On a RED suite the trace/metric/decision record of the run is preserved
# under $CI_ARTIFACTS_DIR (default ci-artifacts/) so failures are
# diagnosable from the span journal, the flight-recorder event journal,
# and a Prometheus snapshot instead of rerun archaeology; ci.yml uploads
# the directory as a workflow artifact.
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-fast}"
ART_DIR="${CI_ARTIFACTS_DIR:-ci-artifacts}"

echo "== lint gate: python -m compileall =="
python -m compileall -q cs230_distributed_machine_learning_tpu tests benchmarks

# CS230_JOURNAL_DIR: every span AND flight-recorder event of the whole
# run lands in ONE journal dir (tests re-root storage per test, which
# would scatter-then-delete them);
# CS230_METRICS_SNAPSHOT: conftest dumps the suite process's registry in
# Prometheus text format at session end when the run failed;
# CS230_EVENTS_SNAPSHOT: conftest dumps the suite process's in-memory
# flight-recorder ring (the scheduling decisions of the failed run) as
# JSONL next to it.
mkdir -p "$ART_DIR"
rc=0
if [ "$MODE" = "kernels" ]; then
  echo "== interpret-mode kernel-parity suite (JAX_PLATFORMS=cpu) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_pallas_logreg.py tests/test_pallas_hist.py \
    tests/test_pallas_mlp.py tests/test_pallas_knn.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
elif [ "$MODE" = "search" ]; then
  echo "== adaptive-search suite (JAX_PLATFORMS=cpu) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_search_asha.py tests/test_search_e2e.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  echo "== adaptive-search benchmark (device-seconds gate) =="
  mkdir -p bench-artifacts
  if JAX_PLATFORMS=cpu python benchmarks/adaptive_search.py \
      > bench-artifacts/adaptive_search.log 2>&1; then
    cp benchmarks/ADAPTIVE_SEARCH.json bench-artifacts/ || true
    tail -n 1 bench-artifacts/adaptive_search.log
  else
    echo "adaptive_search FAILED (see bench-artifacts/adaptive_search.log)"
    rc=1
  fi
elif [ "$MODE" = "perf" ]; then
  echo "== perf observatory: valve A/B + noise-aware gate (quick) =="
  mkdir -p bench-artifacts
  # measure fresh (quick: fewer reps, identical shapes) and gate against
  # the committed baseline; the measurement document is uploaded either way
  if ! JAX_PLATFORMS=cpu python benchmarks/perf_observatory.py \
      --quick --check \
      --out bench-artifacts/PERF_OBSERVATORY.json \
      --baseline benchmarks/PERF_OBSERVATORY.json \
      2>&1 | tee bench-artifacts/perf_observatory.log; then
    echo "perf gate RED (see bench-artifacts/perf_observatory.log)"
    rc=1
  fi
  # all.on (not all): scaling only the fast-path states also shifts the
  # on/off delta, so the drill trips the comparator's cross-host delta
  # mode too — a uniform all= slowdown is, by design, invisible there
  echo "== injected-regression drill: the gate must trip on a synthetic 10x =="
  if PERF_OBS_INJECT="all.on=10.0" JAX_PLATFORMS=cpu \
      python benchmarks/perf_observatory.py \
      --compare-only bench-artifacts/PERF_OBSERVATORY.json \
      --baseline benchmarks/PERF_OBSERVATORY.json \
      > bench-artifacts/perf_inject_drill.log 2>&1; then
    echo "DRILL FAILED: injected regression was NOT caught"
    rc=1
  else
    echo "drill ok: injected regression tripped the gate"
  fi
elif [ "$MODE" = "chaos" ]; then
  echo "== chaos/durability suite (JAX_PLATFORMS=cpu, -m slow) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_chaos.py tests/test_chaos_spmd.py tests/test_cluster.py \
    tests/test_durability.py tests/test_fault_tolerance.py \
    -q -m slow \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  # concurrent-jobs staging benchmark: asserts exactly one upload per
  # (dataset, device) under 8 parallel jobs and refreshes the committed
  # JSON; kept OUTSIDE $ART_DIR so green runs still publish it (ci.yml
  # uploads bench-artifacts/ unconditionally on the chaos job)
  echo "== staging-concurrency benchmark (O(1) uploads contract) =="
  mkdir -p bench-artifacts
  if JAX_PLATFORMS=cpu python benchmarks/staging_concurrency.py \
      > bench-artifacts/staging_concurrency.log 2>&1; then
    cp benchmarks/STAGING_CONCURRENCY.json bench-artifacts/ || true
  else
    echo "staging_concurrency FAILED (see bench-artifacts/staging_concurrency.log)"
    rc=1
  fi
elif [ "$MODE" = "multichip" ]; then
  echo "== elastic trial fabric: mesh cache parity + resharding suites =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_stage_cache.py tests/test_resharding.py \
    tests/test_distributed_mesh.py tests/test_2d_mesh.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  echo "== multichip scaling smoke (forced 1/2 host devices, quick) =="
  mkdir -p bench-artifacts
  if JAX_PLATFORMS=cpu python benchmarks/multichip_bench.py \
      --devices 1,2 --quick --no-check \
      --out bench-artifacts/MULTICHIP_BENCH_smoke.json \
      > bench-artifacts/multichip_smoke.log 2>&1; then
    tail -n 2 bench-artifacts/multichip_smoke.log
  else
    echo "multichip smoke FAILED (see bench-artifacts/multichip_smoke.log)"
    tail -n 20 bench-artifacts/multichip_smoke.log
    rc=1
  fi
  if [ "${MULTICHIP_FULL:-0}" = "1" ]; then
    echo "== FULL multichip scaling curve (1/2/4/8, nightly) =="
    if JAX_PLATFORMS=cpu python benchmarks/multichip_bench.py \
        --out bench-artifacts/MULTICHIP_BENCH_nightly.json \
        > bench-artifacts/multichip_full.log 2>&1; then
      tail -n 5 bench-artifacts/multichip_full.log
    else
      echo "multichip full curve FAILED (see bench-artifacts/multichip_full.log)"
      tail -n 20 bench-artifacts/multichip_full.log
      rc=1
    fi
  fi
elif [ "$MODE" = "streaming" ]; then
  echo "== out-of-core streaming suite (JAX_PLATFORMS=cpu) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_streaming.py tests/test_stage_cache.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  if [ "${STREAMING_FULL:-0}" = "1" ]; then
    # nightly/dispatch: the full-geometry OOM repro (10x budget, both
    # streamed families) + double-buffer overlap profile; the fresh
    # JSON is uploaded for trend-watching (the committed acceptance
    # artifact is benchmarks/STREAMING_MICRO.json)
    echo "== FULL streaming micro-benchmark (OOM repro + overlap) =="
    mkdir -p bench-artifacts
    if JAX_PLATFORMS=cpu python benchmarks/streaming_micro.py \
        > bench-artifacts/streaming_micro.log 2>&1; then
      cp benchmarks/STREAMING_MICRO.json bench-artifacts/ || true
      tail -n 3 bench-artifacts/streaming_micro.log
    else
      echo "streaming_micro FAILED (see bench-artifacts/streaming_micro.log)"
      tail -n 20 bench-artifacts/streaming_micro.log
      rc=1
    fi
  fi
elif [ "$MODE" = "obs" ]; then
  echo "== fleet health plane suites (JAX_PLATFORMS=cpu) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_fleet_health.py tests/test_frontend_aggregation.py \
    tests/test_flight_recorder.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  # live 2-shard overload→fire→drain→resolve drill through the front
  # end; measures fresh and gates on the 8 functional assertions (alert
  # fired, desired>live, journaled fire+resolve, shard attribution, …).
  # Fresh JSON goes to bench-artifacts/ for trend-watching; the shard
  # subprocess logs land under $ART_DIR so a red drill uploads them.
  echo "== fleet health drill (2 shards, overload→fire→drain→resolve) =="
  mkdir -p bench-artifacts
  if FLEET_HEALTH_OUT=bench-artifacts/FLEET_HEALTH.json \
      FLEET_HEALTH_LOG_DIR="$ART_DIR/fleet-health-logs" \
      JAX_PLATFORMS=cpu python benchmarks/fleet_health.py \
      > bench-artifacts/fleet_health.log 2>&1; then
    tail -n 3 bench-artifacts/fleet_health.log
  else
    echo "fleet_health drill FAILED (see bench-artifacts/fleet_health.log)"
    tail -n 20 bench-artifacts/fleet_health.log
    rc=1
  fi
elif [ "$MODE" = "trace" ]; then
  echo "== critical-path / trace-export suites (JAX_PLATFORMS=cpu) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_critpath.py tests/test_trace_propagation.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  # live attribution drill: baseline vs injected aggregate slowdown
  # through a real front end; the fresh JSON is uploaded for
  # trend-watching (the committed acceptance artifact is
  # benchmarks/CRITICAL_PATH.json)
  echo "== critical-path attribution drill (inject -> diff -> attribute) =="
  mkdir -p bench-artifacts
  if CRITICAL_PATH_OUT=bench-artifacts/CRITICAL_PATH.json \
      CS230_JOURNAL_DIR="$ART_DIR/journal" \
      JAX_PLATFORMS=cpu python benchmarks/critical_path.py \
      > bench-artifacts/critical_path.log 2>&1; then
    tail -n 2 bench-artifacts/critical_path.log
  else
    echo "critical_path drill FAILED (see bench-artifacts/critical_path.log)"
    tail -n 20 bench-artifacts/critical_path.log
    rc=1
  fi
  # the drill exports the slowed job's trace as Perfetto Chrome JSON;
  # re-load it here as an independent validity gate (json.load + the
  # Chrome-trace keys ui.perfetto.dev requires)
  echo "== Perfetto export validity gate =="
  if ! python - <<'PYEOF'
import json, sys

doc = json.load(open("bench-artifacts/CRITICAL_PATH.json"))
path = (doc.get("export") or {}).get("perfetto_path")
if not path:
    sys.exit("no perfetto_path recorded in CRITICAL_PATH.json")
trace = json.load(open(path))
events = trace.get("traceEvents")
assert isinstance(events, list) and events, "traceEvents missing/empty"
for e in events:
    assert "ph" in e and "pid" in e and "name" in e, f"malformed event {e}"
    if e["ph"] == "X":
        assert "ts" in e and "dur" in e, f"complete event missing ts/dur {e}"
print(f"perfetto export ok: {len(events)} events in {path}")
PYEOF
  then
    echo "Perfetto validity gate FAILED"
    rc=1
  fi
elif [ "$MODE" = "curves" ]; then
  echo "== trial telemetry plane suites (JAX_PLATFORMS=cpu) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_telemetry_curves.py tests/test_search_e2e.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  if [ "${CURVES_FULL:-0}" = "1" ]; then
    # nightly/dispatch: the full micro-benchmark — capture overhead
    # <= 3% (interleaved on/off pairs), the diverging-lr watchdog drill
    # (< 30% of max_resource consumed), survivor parity under
    # CS230_CURVES=0; the fresh JSON is uploaded for trend-watching
    # (the committed acceptance artifact is benchmarks/CURVE_MICRO.json)
    echo "== FULL curve micro-benchmark (overhead + watchdog gates) =="
    mkdir -p bench-artifacts
    if JAX_PLATFORMS=cpu python benchmarks/curve_micro.py \
        > bench-artifacts/curve_micro.log 2>&1; then
      cp benchmarks/CURVE_MICRO.json bench-artifacts/ || true
      tail -n 3 bench-artifacts/curve_micro.log
    else
      echo "curve_micro FAILED (see bench-artifacts/curve_micro.log)"
      tail -n 20 bench-artifacts/curve_micro.log
      rc=1
    fi
  fi
elif [ "$MODE" = "rebalance" ]; then
  echo "== cross-shard rebalancing suite (JAX_PLATFORMS=cpu) =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest \
    tests/test_rebalance.py \
    -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  if [ "${REBALANCE_FULL:-0}" = "1" ]; then
    # nightly/dispatch: the full skewed-hash load test — even baseline,
    # skew with rebalancing off, skew with rebalancing on — gated on
    # recovery >= 0.8 and the rebalancer actually acting; the fresh
    # JSON is uploaded for trend-watching (the committed acceptance
    # artifact is benchmarks/LOADTEST_SKEW.json)
    echo "== FULL skewed-hash rebalance load test (recovery gate) =="
    mkdir -p bench-artifacts
    if SKEW_OUT=bench-artifacts/LOADTEST_SKEW.json \
        JAX_PLATFORMS=cpu python benchmarks/loadtest_skew.py --check \
        > bench-artifacts/loadtest_skew.log 2>&1; then
      tail -n 2 bench-artifacts/loadtest_skew.log
    else
      echo "loadtest_skew FAILED (see bench-artifacts/loadtest_skew.log)"
      tail -n 20 bench-artifacts/loadtest_skew.log
      rc=1
    fi
  fi
elif [ "$MODE" = "loadtest" ]; then
  # full sharded control-plane load test (nightly/dispatch in ci.yml):
  # 4 shard subprocesses behind 2 front ends, the ROADMAP item 2
  # acceptance harness. Measures only — the committed acceptance artifact
  # (benchmarks/loadtest_4shard.json) is produced on the dev box; this
  # job uploads the fresh run for trend-watching, with the functional
  # smoke assertions (completion + routing) as the only gate.
  echo "== 4-shard control-plane load test (no latency gate) =="
  mkdir -p bench-artifacts
  if LOADTEST_SHARDS=4 LOADTEST_FRONTENDS=2 \
      LOADTEST_OUT=bench-artifacts/loadtest_4shard.json \
      JAX_PLATFORMS=cpu python benchmarks/loadtest.py --smoke \
      > bench-artifacts/loadtest_4shard.log 2>&1; then
    tail -n 2 bench-artifacts/loadtest_4shard.log
  else
    echo "loadtest FAILED (see bench-artifacts/loadtest_4shard.log)"
    rc=1
  fi
else
  echo "== tier-1 fast suite (JAX_PLATFORMS=cpu, -m 'not slow') =="
  CS230_JOURNAL_DIR="$ART_DIR/journal" \
  CS230_METRICS_SNAPSHOT="$ART_DIR/metrics.prom" \
  CS230_EVENTS_SNAPSHOT="$ART_DIR/events_ring.jsonl" \
  JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || rc=$?
  # sharded-topology smoke: 2 shard subprocesses + 1 front end, reduced
  # client count, completion + routing asserted, NO latency gate — the
  # front/core split is exercised on every CI run, not just nightly
  echo "== sharded control-plane smoke (2 shards, 16 clients) =="
  if LOADTEST_SHARDS=2 LOADTEST_FRONTENDS=1 LOADTEST_CLIENTS=16 \
      LOADTEST_JOBS_PER_CLIENT=1 LOADTEST_EXECUTORS=1 \
      LOADTEST_OUT="$ART_DIR/loadtest_smoke.json" \
      JAX_PLATFORMS=cpu python benchmarks/loadtest.py --smoke \
      > "$ART_DIR/loadtest_smoke.log" 2>&1; then
    tail -n 1 "$ART_DIR/loadtest_smoke.log"
  else
    echo "sharded smoke FAILED (see $ART_DIR/loadtest_smoke.log)"
    tail -n 20 "$ART_DIR/loadtest_smoke.log"
    rc=1
  fi
fi

if [ "$rc" -eq 0 ]; then
  # green run: drop the artifacts (only red runs need the forensic record)
  rm -rf "$ART_DIR"
else
  echo "== suite failed (rc=$rc); trace/metric record kept in $ART_DIR =="
  ls -la "$ART_DIR" "$ART_DIR/journal" 2>/dev/null || true
fi
exit "$rc"
